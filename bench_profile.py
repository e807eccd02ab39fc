#!/usr/bin/env python
"""Churn-regime phase-breakdown driver (round-5 verdict weak #1).

The verdict found steady_churn_pps (~5M, bench.py) at ~3x below what the
component numbers predict, with the slow-path loop never profiled.  This
driver reproduces bench.py's churn regime EXACTLY (100k rules + 5k
services, universe == flow slots == 2^22, 1/8 of every 2^17-lane batch
genuinely fresh flows) and attributes the per-step time to named phases
via the cumulative phase-mask chain (models/profile.py): fast-path
lookup, miss-detect scaffolding, ServiceLB, classify, cache commit/DNAT
meta write, eviction scan.

Honesty gate: the phase breakdown sums EXACTLY to the chain-end time by
construction (telescoped differencing), and an INDEPENDENT full-step
measurement (separate dispatch, different K values) must agree within
+-15% — the same criterion as "sums to the measured steady_churn_pps
inverse".  Disagreement beyond that exits nonzero AFTER printing, so the
driver always records the numbers.

Emits one JSON line on stdout and writes PROFILE_r<NN>.json (next free
round number in the repo root; --out overrides).
"""

import argparse
import glob
import json
import os
import re

import jax.numpy as jnp
import numpy as np

from antrea_tpu.compiler.compile import compile_policy_set
from antrea_tpu.compiler.services import compile_services
from antrea_tpu.models import pipeline as pl
from antrea_tpu.models.profile import (FUSED_PHASE_CHAIN,
                                       MAINT_PHASE_CHAIN,
                                       OVERLAP_PHASE_CHAIN, PHASE_CHAIN,
                                       PRUNE_PHASE_CHAIN, profile_churn,
                                       profile_churn_fused,
                                       profile_churn_maintenance,
                                       profile_churn_overlap,
                                       profile_churn_prune)
from antrea_tpu.simulator.genpolicy import gen_cluster
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic
from antrea_tpu.utils import ip as iputil
from antrea_tpu.utils.compile_cache import enable_compile_cache

# bench.py's churn-regime shape, verbatim.
N_RULES = 100_000
N_SERVICES = 5_000
B = 1 << 17
FLOW_SLOTS = 1 << 22
CHURN_POOL = 1 << 22
CHURN_DIV = 8
AGREEMENT_TOL = 0.15


def _next_out(repo_dir: str) -> str:
    taken = [
        int(m.group(1))
        for p in glob.glob(os.path.join(repo_dir, "PROFILE_r*.json"))
        if (m := re.search(r"PROFILE_r(\d+)\.json$", p))
    ]
    return os.path.join(repo_dir, f"PROFILE_r{max(taken, default=0) + 1:02d}.json")


def _cols(tr):
    return (
        jnp.asarray(np.ascontiguousarray(iputil.flip_u32(tr.src_ip))),
        jnp.asarray(np.ascontiguousarray(iputil.flip_u32(tr.dst_ip))),
        jnp.asarray(np.ascontiguousarray(tr.proto)),
        jnp.asarray(np.ascontiguousarray(tr.src_port)),
        jnp.asarray(np.ascontiguousarray(tr.dst_port)),
    )


def _telemetry_structure_check(out_path: str) -> int:
    """--mode telemetry: the hot-path telemetry schema gate on BOTH
    engines (observability/telemetry.py).  A toy world (this is a
    structure check, not a measurement): each twin runs one instrumented
    probe step via profile(mode="telemetry") and both counter key sets
    must equal TELEMETRY_COUNTERS — the same invariant the
    telemetry-registry analysis pass pins statically, checked here
    against the LIVE kernels."""
    from antrea_tpu.datapath.oracle_dp import OracleDatapath
    from antrea_tpu.datapath.tpuflow import TpuflowDatapath
    from antrea_tpu.observability.telemetry import TELEMETRY_COUNTERS

    cluster = gen_cluster(1_000, n_nodes=8, pods_per_node=8, seed=1)
    tr = gen_traffic(cluster.pod_ips, 1 << 10, n_flows=1 << 8, seed=3)
    counters = {}
    for name, dp in (
        ("tpuflow", TpuflowDatapath(cluster.ps, flow_slots=1 << 12,
                                    aff_slots=1 << 10)),
        ("oracle", OracleDatapath(cluster.ps, flow_slots=1 << 12,
                                  aff_slots=1 << 10)),
    ):
        p = dp.profile(tr, mode="telemetry")
        counters[name] = p["counters"]
    want = sorted(TELEMETRY_COUNTERS)
    ok = all(sorted(c) == want for c in counters.values())
    doc = {
        "metric": "telemetry_structure_check",
        "mode": "telemetry",
        "expected_counters": want,
        "engines": counters,
        "ok": ok,
    }
    line = json.dumps(doc)
    print(line)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    print(f"# wrote {out_path}", flush=True)
    if not ok:
        raise SystemExit(
            f"telemetry counter schema drifted from TELEMETRY_COUNTERS "
            f"{want}: {({n: sorted(c) for n, c in counters.items()})}"
        )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--k-small", type=int, default=4)
    ap.add_argument("--k-big", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument(
        "--mode", choices=("sync", "overlap", "maintenance", "prune",
                           "fused", "telemetry"),
        default="sync",
        help="sync = the inline slow-path chain (PHASE_CHAIN); overlap = "
             "the round-6 double-buffered regime (OVERLAP_PHASE_CHAIN: "
             "drain of window i-1 overlapping fast step i) — diff the "
             "two runs to attribute the overlap win phase by phase; "
             "maintenance = the unified background plane's cadence "
             "(MAINT_PHASE_CHAIN: the scheduler's fused maintenance pass "
             "riding every step) — maintenance_s is the plane's own "
             "attributed cost; prune = the round-7 two-level kernel's "
             "regime (PRUNE_PHASE_CHAIN: the async cadence over a "
             "prune_budget>0 meta, classify split into summary-gather vs "
             "candidate-gather); fused = the round-8 one-kernel regime "
             "(FUSED_PHASE_CHAIN: the async cadence over a one-pass "
             "meta — the fused_onepass entry is the whole in-VMEM pass); "
             "telemetry = the hot-path counter STRUCTURE check "
             "(observability/telemetry.py): one instrumented probe step "
             "on BOTH engines, both twins' counter key sets pinned to "
             "TELEMETRY_COUNTERS — a schema gate, not a measurement",
    )
    ap.add_argument("--prune-budget", type=int, default=4,
                    help="K budget for --mode prune/fused "
                         "(PRUNE_LADDER rung)")
    args = ap.parse_args()
    enable_compile_cache()
    out_path = args.out or _next_out(os.path.dirname(os.path.abspath(__file__)))

    if args.mode == "telemetry":
        return _telemetry_structure_check(out_path)

    cluster = gen_cluster(N_RULES, n_nodes=64, pods_per_node=32, seed=1)
    cps = compile_policy_set(cluster.ps)
    services = gen_services(N_SERVICES, cluster.pod_ips, seed=2)
    svc = compile_services(services)
    # Hot set: zipf repeat-flow traffic (the established connections);
    # pool: one packet per universe flow, no repeats (bench.measure_churn's
    # permutation pool — a zipf pool re-hits its head and under-states the
    # miss fraction).
    hot = gen_traffic(cluster.pod_ips, B, n_flows=1 << 15, seed=31,
                      services=services, svc_fraction=0.3)
    pool = gen_traffic(cluster.pod_ips, CHURN_POOL, n_flows=CHURN_POOL,
                       seed=32, services=services, svc_fraction=0.3,
                       one_per_flow=True)
    step, state, (drs, dsvc) = pl.make_pipeline(
        cps, svc, flow_slots=FLOW_SLOTS, miss_chunk=4096, fused=True,
        prune_budget=(args.prune_budget
                      if args.mode in ("prune", "fused") else 0),
        # --mode prune pins the STAGED pruned kernel (fused=True +
        # prune_budget>0 would otherwise auto-upgrade to the one-pass,
        # which --mode fused profiles instead).
        onepass=args.mode == "fused",
    )
    hot_c, pool_c = _cols(hot), _cols(pool)
    n_new = B // CHURN_DIV

    if args.mode == "overlap":
        chain = OVERLAP_PHASE_CHAIN
        prof = profile_churn_overlap(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=args.k_small, k_big=args.k_big, repeats=args.repeats,
        )
        # Independent full-step measurement of the SAME overlapped
        # cadence: a 2-entry chain whose end is the full (fast + drain
        # at PH_ALL) step, fresh dispatches, different K values.
        indep = profile_churn_overlap(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=max(2, args.k_small // 2), k_big=2 * args.k_big,
            repeats=args.repeats,
            chain=(("base", 0), ("full", pl.PH_ALL)),
        )
    elif args.mode == "maintenance":
        chain = MAINT_PHASE_CHAIN
        prof = profile_churn_maintenance(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=args.k_small, k_big=args.k_big, repeats=args.repeats,
        )
        # Independent full-step measurement of the SAME maintenance
        # cadence (rider included): fresh dispatches, different K values.
        indep = profile_churn_maintenance(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=max(2, args.k_small // 2), k_big=2 * args.k_big,
            repeats=args.repeats,
            chain=(("base", 0), ("full", pl.PH_ALL)),
        )
    elif args.mode == "fused":
        chain = FUSED_PHASE_CHAIN
        prof = profile_churn_fused(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=args.k_small, k_big=args.k_big, repeats=args.repeats,
        )
        # Independent full-step measurement of the SAME one-kernel
        # cadence: fresh dispatches, different K values.
        indep = profile_churn_fused(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=max(2, args.k_small // 2), k_big=2 * args.k_big,
            repeats=args.repeats,
            chain=(("base", 0), ("full", pl.PH_ALL)),
        )
    elif args.mode == "prune":
        chain = PRUNE_PHASE_CHAIN
        prof = profile_churn_prune(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=args.k_small, k_big=args.k_big, repeats=args.repeats,
        )
        # Independent full-step measurement of the SAME pruned cadence:
        # fresh dispatches, different K values.
        indep = profile_churn_prune(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=max(2, args.k_small // 2), k_big=2 * args.k_big,
            repeats=args.repeats,
            chain=(("base", 0), ("full", pl.PH_ALL)),
        )
    else:
        chain = PHASE_CHAIN
        prof = profile_churn(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=args.k_small, k_big=args.k_big, repeats=args.repeats,
        )
        # Independent full-step measurement: fresh dispatch chain,
        # different K values — the cross-check that the masked-chain end
        # is a real full-step time, not an artifact of its own
        # measurement.
        indep = profile_churn(
            step.meta, state, drs, dsvc, hot_c, pool_c, n_new=n_new,
            k_small=max(2, args.k_small // 2), k_big=2 * args.k_big,
            repeats=args.repeats, chain=(("full", pl.PH_ALL),),
        )
    sum_phases = sum(prof["phases_s"].values())
    agreement = sum_phases / indep["total_s"]
    bottleneck = max(prof["phases_s"], key=prof["phases_s"].get)
    doc = {
        "metric": f"churn_phase_breakdown_{N_RULES // 1000}k_rules",
        "unit": "s/step",
        "mode": args.mode,
        "batch": B,
        "fresh_per_step": n_new,
        "churn_universe": CHURN_POOL,
        "flow_slots": FLOW_SLOTS,
        "phase_chain": [name for name, _m in chain],  # PHASE_CHAIN / OVERLAP_PHASE_CHAIN per --mode
        "phases_s": prof["phases_s"],
        "phase_fractions": prof["phase_fractions"],
        "total_s": prof["total_s"],
        "churn_pps": prof["pps"],
        "bottleneck": bottleneck,
        # Maintenance mode only: the background plane's own attributed
        # per-step cost (maint_fast_path minus a rider-free fast step).
        "maintenance_s": prof.get("maintenance_s"),
        "maintenance_fraction": prof.get("maintenance_fraction"),
        # Prune mode only: the K budget the chain was attributed at.
        "prune_budget": prof.get("prune_budget"),
        "check": {
            "sum_phases_s": sum_phases,
            "independent_step_s": indep["total_s"],
            "independent_churn_pps": indep["pps"],
            "agreement": round(agreement, 4),
            "within_15pct": abs(agreement - 1.0) <= AGREEMENT_TOL,
        },
    }
    line = json.dumps(doc)
    print(line)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    print(f"# wrote {out_path}", flush=True)
    if abs(agreement - 1.0) > AGREEMENT_TOL:
        raise SystemExit(
            f"phase breakdown ({sum_phases:.4f}s) disagrees with the "
            f"independent step time ({indep['total_s']:.4f}s) by more than "
            f"{AGREEMENT_TOL:.0%} — measurement unstable, do not trust the "
            f"attribution"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
