"""A generator that a later configuration might bring: `policy_flows` with
one more column of the batch, `pkt_len` (the mix's, on every lane), which
changes no verdict and reaches the comparison's sample like every column."""
import os

import numpy as np

from manifest import load_module

_flows = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "policy_flows.py"))
class_shares = _flows.class_shares


class Traffic(_flows.Traffic):
    def __init__(self, params: dict, world, seed: int, reference):
        super().__init__(params, world, seed, reference)
        self._len = np.full(self.batch, int(params["pkt_len"]), np.int32)

    def next_batch(self):
        cols, lanes, fresh = super().next_batch()
        return dict(cols, pkt_len=self._len), lanes, fresh

    def warmup(self):
        for cols in super().warmup():
            yield dict(cols, pkt_len=self._len)
