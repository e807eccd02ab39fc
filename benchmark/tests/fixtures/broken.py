"""Engines with the timed path broken underneath, for test_harness.py: each
fault has to turn `correct` false.  Each wraps the real engine."""
import dataclasses

import numpy as np

from antrea_tpu.datapath import make_datapath
from antrea_tpu.packet import PacketBatch


class _Wrapped:
    def __init__(self, **kw):
        self.dp = make_datapath("tpuflow", **kw)

    def install_bundle(self, ps, services):
        return self.dp.install_bundle(ps, services)


class _FlipCode(_Wrapped):
    """An answer altered where it is produced: every 7th lane's verdict."""

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        code = np.array(res.code)
        code[::7] = (code[::7] + 1) % 3
        return dataclasses.replace(res, code=code)


class _HalfBatch(_Wrapped):
    """Half of the batch left out: the second half is never classified and
    comes back as the zero image (allowed, nothing committed)."""

    def step(self, batch, now):
        h = batch.size // 2
        half = PacketBatch(**{f: getattr(batch, f)[:h] for f in (
            "src_ip", "dst_ip", "proto", "src_port", "dst_port")})
        res = self.dp.step(half, now)
        out = {}
        for f in dataclasses.fields(res):
            v = getattr(res, f.name)
            if isinstance(v, np.ndarray):
                pad = np.zeros(batch.size - h, v.dtype)
                if f.name == "svc_idx":
                    pad -= 1
                if f.name in ("dnat_ip", "dnat_port"):
                    pad = getattr(batch, "dst_ip" if f.name == "dnat_ip"
                                  else "dst_port")[h:].astype(v.dtype)
                out[f.name] = np.concatenate([v, pad])
            elif isinstance(v, list):
                out[f.name] = v + [None] * (batch.size - h)
        return dataclasses.replace(res, **out)


class _StateUnchanged(_Wrapped):
    """A step that returns its state unchanged: nothing is ever committed."""

    def step(self, batch, now):
        state = self.dp._state
        res = self.dp.step(batch, now)
        self.dp._state = state
        return res


class _ReplyUnmarked(_Wrapped):
    """A reply leg answered without its mark: `reply` 0 on every lane that
    the cache answered from a connection's reply entry."""

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        return dataclasses.replace(res, reply=np.zeros_like(res.reply))


class _ReplyAtEndpoint(_Wrapped):
    """The un-DNAT left out: a reply leg keeps the endpoint it came from in
    `dnat_ip`/`dnat_port` instead of the frontend its client sent to."""

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        rpl = np.asarray(res.reply) == 1
        return dataclasses.replace(
            res,
            dnat_ip=np.where(rpl, batch.src_ip, res.dnat_ip).astype(
                np.asarray(res.dnat_ip).dtype),
            dnat_port=np.where(rpl, batch.src_port, res.dnat_port).astype(
                np.asarray(res.dnat_port).dtype))


def flip_code(**kw):
    return _FlipCode(**kw)


def half_batch(**kw):
    return _HalfBatch(**kw)


def state_unchanged(**kw):
    return _StateUnchanged(**kw)


def reply_unmarked(**kw):
    return _ReplyUnmarked(**kw)


def reply_at_endpoint(**kw):
    return _ReplyAtEndpoint(**kw)
