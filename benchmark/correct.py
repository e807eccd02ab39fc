"""The comparison that decides `correct`.

What is compared is what the timed window itself produced: a sample of lanes
of every step of the window (drawn from the seed before any answer existed),
each lane's StepResult fields against the plain reference, once the window
has closed.  Numbers, each with a limit of its own (the traffic file's
`limits`; PERF.md gives the readings they were set from):

  wrong_lanes         sampled lanes on which an exact statement fails
                      (limit 0).  The statements come with the reference: a
                      reference that defines `failed_statements(sample)`
                      (manifest.py: `references/<name>.py`) states its
                      deployment's own (reply legs, wide keys); one that
                      does not gets `failed_statements` below, the first
                      deployments' nine (either fills the sample's
                      `ref_code` and `ref_rule`, which the lines for
                      standard error print).  Per lane: the Service resolved;
                      the DNAT target is one of its endpoints (or untouched);
                      `code` equals the reference's verdict on the post-DNAT
                      packet (REJECT where the Service has no endpoint); the
                      rule named for a denial is the reference's; reject_kind
                      follows code and protocol; no reply or SNAT mark on
                      this one-directional ClusterIP traffic; `est` only on
                      an allowed flow that was sent in an earlier step;
                      `committed` exactly on an allowed lane that is not
                      `est`; a packet to a multicast group (224.0.0.0/4)
                      bypasses conntrack as in Antrea's multicast pipeline:
                      classified every time, never committed, never `est`.
  short_miss_steps    steps that reported fewer misses than they had lanes
                      never sent before (limit 0).
  remiss_share        of all the window's lanes of flows sent before, the
                      share that missed the cache: the steps' n_miss less
                      their lanes never sent before, over the other lanes.
                      Direct-mapped cache collisions and evictions make it
                      more than 0; an engine that stopped committing reads 1.
  replay_unhit_share  mixes with fresh lanes only: the window's last batch is
                      stepped once more after the close; of its fresh lanes
                      that were committed, the share not established then.
                      A step that returns its state unchanged reads 1.
  reply_unanswered_share
                      only where the generator labels `leg` (1 on a lane
                      sent as the reply leg of a connection the generator
                      heard was committed, 0 on any other): of the sampled
                      reply lanes, the share the program answered with
                      `reply` 0, as a packet of its own.  A reference
                      cannot state "answered as a reply" lane by lane: a
                      direct-mapped cache may rightly lose a reply's entry
                      to another flow, and the reply is then a packet of its
                      own, held to the statements of any other lane.  A
                      window can state it as a share: a program that keeps
                      no reply entries, or answers replies without their
                      mark, reads 1.  A sample with `leg` but no reply lane
                      proves nothing and raises.

The three step-level numbers, the reply number where `leg` is labelled, and
the limits' handling are the same for every cell: a reference may state more
about a lane, it cannot drop one of them.  What a reference's statements may
read: the sample's batch columns, its answers, `fresh`, and the lane labels
its generator gave (manifest.py), each under its own name;
`failed_statements` below reads none of the labels, and `decide` reads `leg`
alone.
"""

from __future__ import annotations

import numpy as np

PROTO_TCP = 6
ALLOW, DROP, REJECT = 0, 1, 2
REPLY_LEG = 1  # the lane label `leg` on a reply leg


def _u32(a):
    return np.asarray(a).astype(np.int64) & 0xFFFFFFFF


def failed_statements(ref, s: dict) -> dict:
    """-> {statement: bool per sampled lane, True where it fails}, and fills
    s["ref_code"]."""
    src, dst = _u32(s["src_ip"]), _u32(s["dst_ip"])
    proto = np.asarray(s["proto"], np.int64)
    dport = np.asarray(s["dst_port"], np.int64)
    code = np.asarray(s["code"], np.int64)
    est = np.asarray(s["est"], np.int64)
    committed = np.asarray(s["committed"], np.int64)
    dnat_ip, dnat_port = _u32(s["dnat_ip"]), np.asarray(s["dnat_port"],
                                                        np.int64)
    fresh = np.asarray(s["fresh"], bool)
    bad = {}

    svc, no_ep = ref.resolve(dst, proto, dport)
    bad["service"] = np.asarray(s["svc_idx"], np.int64) != svc
    untouched = (dnat_ip == dst) & (dnat_port == dport)
    lb = (svc >= 0) & ~no_ep
    bad["dnat"] = np.where(lb, ~ref.is_endpoint(np.maximum(svc, 0), dnat_ip,
                                                dnat_port), ~untouched)

    # Policy on the post-DNAT packet, once per distinct packet.
    pkt = np.stack([src, dnat_ip, proto, dnat_port], axis=1)
    uniq, inv = np.unique(pkt, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    u_code, u_by, u_rule = ref.classify(uniq[:, 0], uniq[:, 1], uniq[:, 2],
                                        uniq[:, 3])
    ref_code = np.where(no_ep, REJECT, u_code[inv])
    s["ref_code"] = ref_code
    bad["code"] = code != ref_code

    in_rule = np.asarray(s["ingress_rule"], object)
    out_rule = np.asarray(s["egress_rule"], object)
    by, rule = u_by[inv], u_rule[inv]
    named = np.where(by == "Out", out_rule, in_rule)
    denied = (ref_code != ALLOW) & ~no_ep
    bad["rule"] = (denied & (named != rule)) | (
        no_ep & ((in_rule != None) | (out_rule != None)))  # noqa: E711
    s["ref_rule"] = np.where(denied, rule, None)

    want_kind = np.where(code == REJECT,
                         np.where(proto == PROTO_TCP, 1, 2), 0)
    bad["reject_kind"] = np.asarray(s["reject_kind"], np.int64) != want_kind
    bad["marks"] = (np.asarray(s["reply"], np.int64) != 0) | (
        np.asarray(s["snat"], np.int64) != 0)
    multicast = (dst >> 28) == 0xE
    bad["est"] = ~np.isin(est, (0, 1)) | (
        (est == 1) & ((code != ALLOW) | fresh | multicast))
    bad["committed"] = committed != (
        (code == ALLOW) & (est == 0) & ~multicast).astype(np.int64)
    return bad


def describe_wrong(bad: dict, s: dict, limit: int = 8) -> list:
    """Lines for standard error: which statements failed on how many lanes,
    and the first wrong lanes with what the program and the reference said."""
    lines = ["wrong by statement: " + ", ".join(
        f"{k} {int(v.sum())}" for k, v in bad.items() if v.any())]
    fields = ("src_ip", "dst_ip", "proto", "src_port", "dst_port", "fresh",
              "code", "ref_code", "est", "committed", "svc_idx", "dnat_ip",
              "dnat_port", "reject_kind", "reply", "snat", "ingress_rule",
              "egress_rule", "ref_rule")
    for i in np.nonzero(np.logical_or.reduce(list(bad.values())))[0][:limit]:
        lines.append(f"lane {i}: " + " ".join(
            [k for k, v in bad.items() if v[i]]) + " | " + " ".join(
            f"{f}={s[f][i]}" for f in fields))
    return lines


def decide(ref, sample: dict, steps: dict, replay, limits: dict):
    """-> (correct, {name: {"value": v, "limit": l}} in the order they are
    printed, lines that describe the wrong lanes if there are any)."""
    numbers = {}

    def hold(name, value):
        if name not in limits:
            raise KeyError(f"the traffic file gives no limit for {name!r}")
        numbers[name] = {"value": value, "limit": limits[name]}

    n = len(sample["code"])
    if n == 0:
        raise ValueError("the window closed with no lane to compare")
    own = getattr(ref, "failed_statements", None)
    bad = own(sample) if own is not None else failed_statements(ref, sample)
    wrong = int(np.logical_or.reduce(list(bad.values())).sum())
    hold("wrong_lanes", wrong)
    hold("short_miss_steps", int(np.sum(
        np.asarray(steps["n_miss"]) < np.asarray(steps["fresh_lanes"]))))
    fresh = np.asarray(sample["fresh"], bool)
    sent_before = int(np.sum(steps["lanes"]) - np.sum(steps["fresh_lanes"]))
    if sent_before:
        hold("remiss_share", float(
            (np.sum(steps["n_miss"]) - np.sum(steps["fresh_lanes"]))
            / sent_before))
    if replay is not None:
        was = np.asarray(replay["committed"]) == 1
        if not was.any():
            raise ValueError("no fresh lane of the last step was committed: "
                             "the replay proves nothing")
        hold("replay_unhit_share", float(np.mean(
            np.asarray(replay["est_again"])[was] == 0)))
    legs = None
    if "leg" in sample:
        legs = np.asarray(sample["leg"]) == REPLY_LEG
        if not legs.any():
            raise ValueError("no reply lane sampled: the number proves "
                             "nothing")
        hold("reply_unanswered_share", float(np.mean(
            np.asarray(sample["reply"])[legs] == 0)))
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    est = np.asarray(sample["est"])
    # What the sample covered (no limit: information for the reader).
    served = float(np.sum(steps["lanes"]))
    for name in ("allowed", "established"):  # of ALL the window's lanes
        if name in steps:
            numbers[f"window_{name}_share"] = {
                "value": steps[name] / served, "limit": None}
    numbers["lanes_compared"] = {"value": n, "limit": None}
    for name, mask in (
            ("lanes_established", est == 1),
            ("lanes_cached_denial", ~fresh & (sample["ref_code"] != ALLOW)),
            ("lanes_fresh", fresh),
            ("lanes_service", np.asarray(sample["svc_idx"]) >= 0)):
        numbers[name] = {"value": int(np.sum(mask)), "limit": None}
    if legs is not None:
        numbers["lanes_reply"] = {"value": int(np.sum(legs)), "limit": None}
    return correct, numbers, describe_wrong(bad, sample) if wrong else []
