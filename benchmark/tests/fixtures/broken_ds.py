"""Dual-stack engines with a fault planted on their v6 lanes, for
test_dual_stack.py: each has to turn `correct` false through the dual-stack
reference's own statements.  (`broken.state_unchanged` takes the
configuration's keywords, `dual_stack` among them, and needs no twin.)"""
import numpy as np

from broken import _Wrapped


def _some_v6(batch):
    return np.nonzero(batch.is6)[0][::7]


class _FlipCode6(_Wrapped):
    """A verdict altered on every 7th v6 lane, and on no v4 lane."""

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        at, code = _some_v6(batch), np.array(res.code)
        code[at] = (code[at] + 1) % 3
        res.code = code
        return res


class _Service6(_Wrapped):
    """Every 7th v6 lane answered as if it had reached Service 0."""

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        svc = np.array(res.svc_idx)
        svc[_some_v6(batch)] = 0
        res.svc_idx = svc
        return res


def flip_code6(**kw):
    return _FlipCode6(**kw)


def service6(**kw):
    return _Service6(**kw)
