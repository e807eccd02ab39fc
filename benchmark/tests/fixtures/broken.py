"""Engines with the timed path broken underneath, for test_harness.py: each
fault has to turn `correct` false.  Each wraps the real engine."""
import dataclasses

import numpy as np

from antrea_tpu.datapath import make_datapath
from antrea_tpu.oracle.interpreter import Oracle
from antrea_tpu.packet import Packet, PacketBatch

ALLOW, REJECT = 0, 2


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


class _NoReplyEntries(_Wrapped):
    """A program that keeps no reply entries.  On every lane the real engine
    answered from a connection's reply entry (`reply` 1) it answers what
    such a program would: the reply packet's own policy verdict (the
    program's scalar `Oracle` on the packet as it is, endpoint to client, no
    Service, no un-DNAT), committed and counted as a miss of the step the
    first time that reply is seen, established and from the cache after.
    Every such lane meets the statements a reference holds a reply to whose
    entry the cache lost: only `reply_unanswered_share` sees it."""

    def install_bundle(self, ps, services):
        self._oracle = Oracle(ps)
        self._verdicts = {}  # reply tuple -> (code, ingress rule, egress rule)
        return super().install_bundle(ps, services)

    def step(self, batch, now):
        res = self.dp.step(batch, now)
        lanes = np.nonzero(np.asarray(res.reply) == 1)[0]
        if not len(lanes):
            return res
        code, est = np.array(res.code), np.array(res.est)
        committed, svc = np.array(res.committed), np.array(res.svc_idx)
        dnat_ip, dnat_port = np.array(res.dnat_ip), np.array(res.dnat_port)
        rule_in, rule_out = list(res.ingress_rule), list(res.egress_rule)
        reject = np.array(res.reject_kind)
        first = 0
        for i in lanes:
            t = tuple(int(getattr(batch, c)[i]) for c in (
                "src_ip", "dst_ip", "proto", "src_port", "dst_port"))
            seen = t in self._verdicts
            if not seen:
                first += 1
                v = self._oracle.classify(Packet(*t))
                self._verdicts[t] = (int(v.code), v.ingress.rule,
                                     v.egress.rule)
            c, r_in, r_out = self._verdicts[t]
            code[i], svc[i] = c, -1
            reject[i] = (1 if t[2] == 6 else 2) if c == REJECT else 0
            est[i] = int(seen and c == ALLOW)
            committed[i] = int(c == ALLOW and not seen)
            dnat_ip[i], dnat_port[i] = t[1], t[4]
            rule_in[i], rule_out[i] = r_in, r_out
        return dataclasses.replace(
            res, code=code, est=est, committed=committed, svc_idx=svc,
            dnat_ip=dnat_ip, dnat_port=dnat_port, ingress_rule=rule_in,
            egress_rule=rule_out, reject_kind=reject,
            reply=np.zeros_like(res.reply), n_miss=res.n_miss + first)


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


def no_reply_entries(**kw):
    return _NoReplyEntries(**kw)
