#!/usr/bin/env python3
"""The control of the comparison: the reference put in the program's place
with one stated guarantee broken, and it has to come out as not correct.

The guarantee: every verdict is the first match over ALL installed rules,
exact.  The step that would tempt a later PR is an approximate
classification (a pruned candidate set with no fallback, a digest of the rule
set).  The control classifies each sampled lane's post-DNAT packet over every
second policy only, and answers with that verdict and all that follows from
it (the rule named, reject_kind, committed).  Those answers go through the
same `correct.decide` as the program's, against the full reference: it has to
say False, and `wrong_lanes` is the control's reading (limit 0).

On the chip, at a cell's own size, one run reads the program and the control:

    python3 benchmark/tests/control.py --workload np100k.churn --seed 3 --seconds 10

The benchmark's own runs never run it.  tests/test_harness.py keeps it as a
test at a size the CPU holds.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import correct  # noqa: E402


def read(ctx: dict) -> dict:
    s = dict(ctx["sample"])
    # The control is built from the class of the reference it was handed:
    # a configuration that brings its own reference brings its control.
    half = type(ctx["reference"])(ctx["world"],
                                  keep_policy=lambda i: i % 2 == 0)
    proto = np.asarray(s["proto"], np.int64)
    _, no_ep = half.resolve(correct._u32(s["dst_ip"]), proto,
                            np.asarray(s["dst_port"], np.int64))
    code, by, rule = half.classify(
        correct._u32(s["src_ip"]), correct._u32(s["dnat_ip"]), proto,
        np.asarray(s["dnat_port"], np.int64))
    code = np.where(no_ep, correct.REJECT, code)
    rule = np.where(no_ep, None, rule)
    est = np.asarray(s["est"]) * (code == correct.ALLOW)
    s.update(
        code=code, est=est,
        committed=((code == correct.ALLOW) & (est == 0)
                   & ((correct._u32(s["dst_ip"]) >> 28) != 0xE)
                   ).astype(np.int64),
        reject_kind=np.where(code == correct.REJECT,
                             np.where(proto == correct.PROTO_TCP, 1, 2), 0),
        egress_rule=np.where(by == "Out", rule, None),
        ingress_rule=np.where(by == "In", rule, None))
    ok, numbers, _ = correct.decide(ctx["reference"], s, ctx["steps"],
                                    ctx["replay"], ctx["limits"])
    return {"correct": bool(ok), "lanes": len(code),
            "wrong_lanes": numbers["wrong_lanes"]["value"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import harness

    result = harness.run_cell(args.workload, args.seed, args.seconds, False,
                              t_process=T_PROCESS, after_check=read)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
