"""A reference that a later configuration might bring
(`benchmark/references/<name>.py`, found by the configuration's `reference`
key): the first deployments' reference, which reads only the world its own
builder made (`marked_world.py`) and states one thing more about a lane,
from a column that only its own generator sends: no packet is longer than
the pod network's MTU."""
import numpy as np

import correct
import reference

MTU = 1500


class Reference(reference.Reference):
    def __init__(self, world, keep_policy=None):
        if not all(n.startswith("marked-") for n in world.nodes):
            raise ValueError("this reference reads the marked world only")
        super().__init__(world, keep_policy)

    def failed_statements(self, s: dict) -> dict:
        bad = correct.failed_statements(self, s)
        bad["pkt_len"] = np.asarray(s["pkt_len"], np.int64) > MTU
        return bad
