"""A world builder that a later configuration might bring
(`benchmark/worlds/<name>.py`, found by the configuration's `world_builder`
key): the first deployments' world with every node renamed, which is the
mark that `marked_reference.py` asks for.  Here to show that a world is found
by name with no edit to the harness."""
import dataclasses

import world

MARK = "marked-"

to_program = world.to_program


def build_world(params: dict, seed: int):
    w = world.build_world(params, seed)
    rename = {n: MARK + n for n in w.nodes}
    return dataclasses.replace(
        w, nodes=[rename[n] for n in w.nodes],
        groups=[tuple((ip, rename[node], pod) for ip, node, pod in g)
                for g in w.groups])
