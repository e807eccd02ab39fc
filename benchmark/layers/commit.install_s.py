"""Wall of engine construction and `install_bundle` (compile -> canary ->
swap -> settle) during set-up."""


def read(ctx):
    return ctx["install_s"]
