"""One run of one cell: set-up, the measured window, the comparison, the line.

The entry the window drives is `engine.step(batch, now)` of the engine that
the configuration's `entry` builds — the served path, timed from the
client's side around the whole call.  One process, no child, no thread.

Nothing here knows a cell, a configuration, a traffic mix or a per-layer
metric by name: those are files that `manifest.py` finds.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib
import os
import shutil
import statistics
import sys
import time
import traceback

import numpy as np

import correct
import reduce_trace
from manifest import HERE, Manifest, load_json, load_module

# What is taken from every sampled lane of a StepResult; of the batch, every
# column the generator sent (Window.take).
_ANSWER_FIELDS = ("code", "est", "committed", "svc_idx", "dnat_ip",
                  "dnat_port", "reply", "reject_kind", "snat",
                  "ingress_rule", "egress_rule")
# `now` handed to step: whole seconds of the run's wall clock from a fixed
# base, so that conntrack aging runs as on a node.
_NOW_BASE = 1000
# A traced run keeps the profiler on for this long, from a quarter of the
# window on; traces are large and tracing slows the host.
_TRACE_SECONDS = 3.0
# A traced run puts the program's step records aside every so many steps:
# the program's ring keeps its last 4,096, and a window of short steps has
# more (the per-layer readers are held to the WHOLE window, step_spans.py).
_KEEP_RECORDS_EVERY = 1024


class NoDevice(SystemExit):
    """The gate: wrong platform, unknown device kind or too few chips."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_gate(platform: str, chips: int, peaks: dict) -> tuple:
    import jax

    found = jax.default_backend()
    if found != platform:
        raise NoDevice(f"bench: the default JAX backend is {found!r}, the "
                       f"cell needs {platform!r}; nothing was run")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}; nothing was run")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoDevice(f"bench: device kind {kind!r} is not in peaks.json "
                       f"({sorted(peaks)}); nothing was run")
    return devs[:chips], peaks[kind]


def enable_compile_cache(root: str) -> str:
    """The environment's directory if it names one (JAX reads the variable
    itself; no other is set in code), else a fixed one inside the checkout."""
    import jax

    # The install's canary and digests run eagerly: hundreds of sub-second
    # executables that the default 1 s floor would compile on every start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine(config: dict, devices: list):
    """The configuration's `engine` group is data: a dotted factory, its
    leading arguments, its keywords, and optionally a mesh over the cell's
    chips (given to the factory as `devices`)."""
    spec = config["engine"]
    mod, _, attr = spec["entry"].rpartition(".")
    factory = getattr(importlib.import_module(mod), attr)
    kwargs = dict(spec.get("kwargs", {}))
    if "mesh" in spec:
        kwargs.update(spec["mesh"], devices=devices)
    return factory(*spec.get("args", []), **kwargs)


def now_of(t_process: float) -> int:
    return _NOW_BASE + int(time.perf_counter() - t_process)


def packet_batch(cols: dict):
    from antrea_tpu.packet import PacketBatch

    return PacketBatch(**cols)


class Window:
    """What the client's side saw: one row per iteration of the loop."""

    def __init__(self):
        self.t_assemble = []  # clock when the client began the batch
        self.t_handoff = []  # ... handed it to step
        self.t_verdict = []  # ... had the StepResult
        self.t_done = []  # ... had put its sample of the answers aside
        self.cpu_in_step = []  # the process's CPU seconds inside step
        self.lanes = []
        self.n_miss = []
        self.n_allowed = 0  # lanes of the whole window with code ALLOW
        self.n_established = 0  # ... answered from an established flow
        self.gc = []  # (generation, seconds) of every collection inside it
        self.failed_lanes = 0
        self.error = None
        self.sample = {f: [] for f in _ANSWER_FIELDS + ("fresh",)}
        self.last = None  # (columns, StepResult) of the last step
        self.records = []  # the program's step records, put aside (traced)

    def keep_records(self, engine) -> None:
        read = getattr(engine, "step_trace", None)
        trace = read() if read is not None else None
        if trace:
            self.records.append(trace["records"])

    def take(self, cols: dict, res, lanes, fresh) -> None:
        """The sample carries what the batch carried: every column the
        generator sent that is not None, so that a reference which states
        something about `src_ip6` or `tcp_flags` finds it there."""
        for f, column in cols.items():
            if column is not None:
                self.sample.setdefault(f, []).append(
                    np.asarray(column)[lanes])
        for f in _ANSWER_FIELDS:
            v = getattr(res, f)
            self.sample[f].append(
                np.array([v[i] for i in lanes], object) if isinstance(v, list)
                else np.asarray(v)[lanes])
        self.sample["fresh"].append(fresh)

    def sampled(self) -> dict:
        return {f: (np.concatenate(v) if v else np.zeros(0))
                for f, v in self.sample.items()}


def run_window(engine, traffic, seconds: float, t_process: float,
               trace_dir) -> Window:
    """The closed loop: one client, back to back.  With `trace_dir`, the
    profiler is on from a quarter of the window for _TRACE_SECONDS, and the
    client's two phases are spans of the trace."""
    import jax

    w = Window()
    clock = time.perf_counter
    gc_began = []

    def on_gc(phase, info):
        if phase == "start":
            gc_began.append(clock())
        elif gc_began:
            w.gc.append((info["generation"], clock() - gc_began.pop()))

    gc.callbacks.append(on_gc)
    span = jax.profiler.TraceAnnotation if trace_dir else None
    trace_from = seconds / 4 if trace_dir else float("inf")
    trace_until = None  # set while the profiler is on
    t0 = clock()
    while clock() - t0 < seconds:
        if trace_until is None and clock() - t0 >= trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_from, trace_until = float("inf"), clock() + _TRACE_SECONDS
        elif trace_until is not None and clock() >= trace_until:
            jax.profiler.stop_trace()
            trace_until = None
        tracing = trace_until is not None
        ta = clock()
        with span("bench.assemble") if tracing else contextlib.nullcontext():
            cols, lanes, fresh = traffic.next_batch()
            batch = packet_batch(cols)
        now = now_of(t_process)
        cpu = time.process_time()
        tb = clock()
        try:
            with span("bench.step") if tracing else contextlib.nullcontext():
                res = engine.step(batch, now)
        except Exception:  # the served path raised: its lanes failed
            w.error = traceback.format_exc()
            w.failed_lanes += batch.size
            w.lanes.append(batch.size)
            break
        tc = clock()
        w.cpu_in_step.append(time.process_time() - cpu)
        w.t_assemble.append(ta)
        w.t_handoff.append(tb)
        w.t_verdict.append(tc)
        w.lanes.append(batch.size)
        w.n_miss.append(res.n_miss)
        if res.pending is not None:
            # A provisional verdict is not a verdict.
            w.failed_lanes += int(np.sum(res.pending))
        w.n_allowed += int(np.count_nonzero(np.asarray(res.code) == 0))
        w.n_established += int(np.count_nonzero(res.est))
        w.take(cols, res, lanes, fresh)
        w.last = (cols, res)
        if trace_dir and len(w.t_verdict) % _KEEP_RECORDS_EVERY == 0:
            w.keep_records(engine)
        w.t_done.append(clock())
    gc.callbacks.remove(on_gc)
    if trace_until is not None:
        jax.profiler.stop_trace()
    return w


def p95(values: list) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def end_to_end(w: Window, setup_s: float) -> dict:
    if not w.t_verdict:
        return {"setup_s": setup_s}
    wall = w.t_verdict[-1] - w.t_assemble[0]
    served = sum(w.lanes[:len(w.t_verdict)]) - w.failed_lanes
    waits = [(c - b) * 1e3 for b, c in zip(w.t_handoff, w.t_verdict)]
    return {"served_pps": served / wall, "verdict_p95_ms": p95(waits),
            "setup_s": setup_s}


def replay_last(engine, w: Window, traffic, t_process: float):
    """Step the window's last batch once more (after the close, untimed):
    its fresh lanes that were committed have to be established now."""
    if not traffic.fresh_lanes or w.last is None:
        return None
    cols, res = w.last
    again = engine.step(packet_batch(cols), now_of(t_process))
    return {"committed": np.asarray(res.committed)[traffic.fresh_at],
            "est_again": np.asarray(again.est)[traffic.fresh_at]}


def read_layers(manifest: Manifest, cell: str, ctx: dict) -> dict:
    out = {}
    for m in manifest.metrics_of(cell, "per_layer"):
        reader = load_module(manifest.layer_path(m["name"]))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", manifest: Manifest = None,
             peaks: dict = None, t_process: float = None,
             after_check=None) -> dict:
    """-> the result line's object.  `platform`, `manifest` and `peaks` are
    for the harness's own test, and `after_check` for the control
    (tests/control.py); the command line cannot set them."""
    t_process = time.perf_counter() if t_process is None else t_process
    manifest = manifest or Manifest()
    peaks = peaks or load_json(os.path.join(HERE, "peaks.json"))
    cell = manifest.cell(cell_name)
    config = manifest.config(cell["config"])
    mix = load_json(manifest.traffic_path(cell["traffic"]))
    devices, peak = device_gate(platform, cell["chips"], peaks)
    say(f"cell {cell_name} seed {seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache "
        f"{enable_compile_cache(manifest.root)}")

    # -- set-up ------------------------------------------------------------
    t0 = time.perf_counter()
    worlds = load_module(manifest.world_path(config))
    world = worlds.build_world(config["world"], config["world_seed"])
    ps, services = worlds.to_program(world)
    t_world = time.perf_counter()
    engine = build_engine(config, devices)
    engine.install_bundle(ps, services)
    install_s = time.perf_counter() - t_world
    # The traffic reads the policy (which flows it allows), so the reference
    # is built in set-up; the comparison uses the same one after the close.
    reference = load_module(manifest.reference_path(config)).Reference(world)
    generator = load_module(manifest.generator_path(mix["generator"]))
    traffic = generator.Traffic(mix, world, seed, reference)
    say(traffic.summary)
    t_traffic = time.perf_counter()
    for cols in traffic.warmup():
        engine.step(packet_batch(cols), now_of(t_process))
    # The collector stays on in the window, as in an agent; what set-up built
    # (the world's 100k rule objects, the program's tables) is taken out of
    # its reach, so that a full collection in the window walks the window's
    # own garbage and not the harness's world.
    gc.collect()
    gc.freeze()
    t_ready = time.perf_counter()
    setup_s = t_ready - t_process
    say(f"set-up {setup_s:.1f}s: world {t_world - t0:.1f} install "
        f"{install_s:.1f} traffic {t_traffic - t_world - install_s:.1f} "
        f"warm-up {t_ready - t_traffic:.1f}")

    # -- the window ----------------------------------------------------------
    trace_dir = None
    if trace:
        trace_dir = os.path.join(manifest.root, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = run_window(engine, traffic, seconds, t_process, trace_dir)
    gc.unfreeze()
    if w.error:
        say("step raised:\n" + w.error)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}

    # -- the comparison (after the close; not set-up, not window) ------------
    t_check = time.perf_counter()
    replay = replay_last(engine, w, traffic, t_process)
    sample = w.sampled()
    steps = {"n_miss": w.n_miss, "lanes": w.lanes[:len(w.n_miss)],
             "fresh_lanes": [traffic.fresh_lanes] * len(w.n_miss),
             "allowed": w.n_allowed, "established": w.n_established}
    ok, numbers, notes = correct.decide(reference, sample, steps, replay,
                                        mix["limits"])
    ok = ok and w.error is None
    check_s = time.perf_counter() - t_check

    result = {"correct": bool(ok), "attempted": int(sum(w.lanes)),
              "failed": int(w.failed_lanes)}
    if trace:
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        reduced = reduce_trace.reduce(reduce_trace.load(paths[0], peak))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"window": w, "reduced": reduced, "install_s": install_s,
               "config": config, "mix": mix, "peak": peak, "cell": cell,
               "engine": engine}
        result["metrics"] = read_layers(manifest, cell_name, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["top_gaps"]}
    else:
        wanted = {m["name"]: m["unit"]
                  for m in manifest.metrics_of(cell_name, "end_to_end")}
        result["metrics"] = {k: {"value": v, "unit": wanted[k]}
                             for k, v in end_to_end(w, setup_s).items()
                             if k in wanted}
    result["device"] = device
    result["steps"] = len(w.t_verdict)
    # Mean step wall in each fifth of the window: shows a rate that drifts.
    waits = np.subtract(w.t_verdict, w.t_handoff) * 1e3
    result["step_ms_fifths"] = [float(np.mean(part)) for part in
                                np.array_split(waits, 5) if len(part)]
    # Stalls, attributed: the process's own CPU time inside step per fifth
    # (all its threads; a stretch whose wall grows and whose CPU time does
    # not was spent waiting, not computing), the slowest steps (ms, at which
    # step) and every run of the collector inside the window (count and
    # seconds by generation).
    result["step_cpu_ms_fifths"] = [
        float(np.mean(part)) * 1e3 for part in
        np.array_split(w.cpu_in_step, 5) if len(part)]
    slow = np.argsort(waits)[::-1][:5]
    result["slowest_steps"] = [[int(i), float(waits[i])] for i in slow]
    result["step_ms"] = [round(float(x), 2) for x in waits]
    result["gc"] = {f"gen{g}": [sum(1 for x, _ in w.gc if x == g),
                               float(sum(s for x, s in w.gc if x == g))]
                    for g in (0, 1, 2)}
    result["check_s"] = check_s
    if after_check is not None:
        result["control"] = after_check(
            {"world": world, "sample": sample, "reference": reference,
             "steps": steps, "replay": replay, "limits": mix["limits"]})
    result["check"] = numbers
    for line in notes:
        say(line)
    for name, v in numbers.items():
        say(f"check {name} = {v['value']} (limit {v['limit']})")
    say(f"correct = {ok}; comparison took {check_s:.1f}s")
    return result
