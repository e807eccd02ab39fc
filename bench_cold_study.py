"""Cold-path phase-breakdown study (rounds 4-7; see the study notes in
antrea_tpu/ops/match.py — cases 2-4 re-measure the ROUND-4 gather-bound
decomposition that set the ~7.4M pps ceiling, case 1 is the round-5
fused baseline, cases 5-6 the round-6/7 overlap and pruning studies).

Measures, at the bench's 100k-rule world and B=32k on the real chip:
  1. fused end-to-end cold classification (the shipped path);
  2. the searchsorted phase alone;
  3. searchsorted + 6 row gathers with a reduction fused into the gather
     loops (the hard gather bound);
  4. the AND-in-XLA + 2-input consumer variant (measured dead-end (c));
  5. (round 6) the OVERLAP DECOMPOSITION of the churn step — fast step
     alone, coalesced drain alone, the two serialized per iteration, and
     the two double-buffered (drain of window i-1 behind fast step i,
     drain_reclaim=True) — the in-repo methodology behind the
     steady_churn_overlap_pps bench regime: serialized-minus-overlapped
     IS the recovered serialization, and fast+drain-minus-overlapped
     bounds what further overlap could still buy;
  6. (round 7) the PRUNING DECOMPOSITION of the two-level
     aggregated-bitmap kernel — summary-gather alone (phase 1: aggregate
     rows + AND + short-circuit), the pruned end-to-end walk per K rung
     (candidate gather + fallback included), and the unpruned kernel as
     the fallback-dispatch reference — plus a fallback-rate-vs-K sweep
     over PRUNE_LADDER and a match-density sweep (fraction of lanes with
     any candidate at all), emitted as one decomposition JSON.

Run directly: python bench_cold_study.py  (several minutes on the chip;
the July 2026 runs spread ~15% run to run).  --cases selects a
subset (e.g. --cases 6), --smoke shrinks the world so case 6 proves the
methodology end-to-end on a CPU container (the --force-host-devices
style smoke; on-chip numbers are the driver's to write), and --json sets
the case-6 output path."""
import argparse
import json
from functools import lru_cache

ap = argparse.ArgumentParser()
ap.add_argument("--cases", default="1,2,3,4,5,6",
                help="comma-separated case numbers to run")
ap.add_argument("--smoke", action="store_true",
                help="toy world + tiny batches: CPU-green methodology "
                     "proof, not a measurement")
ap.add_argument("--json", default="COLD_STUDY_prune.json",
                help="case-6 decomposition JSON output path")
args = ap.parse_args()
CASES = {int(c) for c in args.cases.split(",") if c.strip()}

import jax, jax.numpy as jnp, numpy as np  # noqa: E402
from antrea_tpu.compiler.compile import compile_policy_set  # noqa: E402
from antrea_tpu.ops import match as m  # noqa: E402
from antrea_tpu.simulator.genpolicy import gen_cluster  # noqa: E402
from antrea_tpu.simulator.traffic import gen_traffic  # noqa: E402
from antrea_tpu.utils import ip as iputil  # noqa: E402
from antrea_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from antrea_tpu.utils.timing import device_loop_time  # noqa: E402

enable_compile_cache()
SMOKE = args.smoke
B = 1 << (10 if SMOKE else 15)
N_RULES = 3_000 if SMOKE else 100_000
K_SMALL, K_BIG, REPEATS = (2, 4, 1) if SMOKE else (8, 64, 3)

cluster = gen_cluster(N_RULES, n_nodes=64, pods_per_node=32, seed=1)
cps = compile_policy_set(cluster.ps)
drs, meta = m.to_device(cps)
# The fused pallas consumer interprets off-TPU (very slow): the smoke
# exercises the XLA path, the chip runs the shipped fused path.
FUSED = not m.pallas_interpret(meta)
tr = gen_traffic(cluster.pod_ips, B, n_flows=B, seed=3)
src = jnp.asarray(iputil.flip_u32(tr.src_ip))
dst = jnp.asarray(iputil.flip_u32(tr.dst_ip))
proto = jnp.asarray(tr.proto)
dport = jnp.asarray(tr.dst_port)
print("w_in", meta.w_in, "w_out", meta.w_out,
      "NB at", drs.ingress.at.bounds.shape, "peer", drs.ingress.peer.bounds.shape,
      "svc", drs.ingress.svc.bounds.shape, "smoke", SMOKE, flush=True)

def timeit(name, body, carry):
    sec = device_loop_time(body, carry, k_small=K_SMALL, k_big=K_BIG,
                           repeats=REPEATS)
    print(f"{name}: {sec*1e3:.3f} ms/batch -> {B/sec/1e6:.2f}M pps", flush=True)
    return sec

def perturb(dp_, acc):
    return dp_ ^ (acc[0] & 1)

carry = (jnp.zeros(8, jnp.int32), drs, src, dst, proto, dport)

# 1) end-to-end fused (baseline)
def body_full(i, carry):
    acc, drs_, s_, d_, p_, dp_ = carry
    cls = m.classify_batch(drs_, s_, d_, p_, perturb(dp_, acc), meta=meta,
                           fused=FUSED)
    return (acc.at[:1].add(cls["code"].sum(dtype=jnp.int32)), drs_, s_, d_, p_, dp_)
t_full = timeit("end-to-end (unpruned)", body_full, carry) if 1 in CASES else None

# 2) searchsorted phase only (6 dim indices + 2 iso)
def body_ss(i, carry):
    acc, drs_, s_, d_, p_, dp_ = carry
    dp2 = perturb(dp_, acc)
    svc_key = (p_ << 16) | dp2
    tot = jnp.int32(0)
    for tab, x in ((drs_.ingress.at, d_), (drs_.ingress.peer, s_),
                   (drs_.ingress.svc, svc_key), (drs_.egress.at, s_),
                   (drs_.egress.peer, d_), (drs_.egress.svc, svc_key)):
        tot = tot + m._searchsorted_right(tab.bounds, x).sum()
    return (acc.at[:1].add(tot), drs_, s_, d_, p_, dp_)
if 2 in CASES:
    t_ss = timeit("searchsorted only", body_ss, carry)

# 3) gathers only (no consumer): sum of gathered rows (XLA fuses sum into gather)
def body_g(i, carry):
    acc, drs_, s_, d_, p_, dp_ = carry
    dp2 = perturb(dp_, acc)
    svc_key = (p_ << 16) | dp2
    tot = jnp.uint32(0)
    for tab, x in ((drs_.ingress.at, d_), (drs_.ingress.peer, s_),
                   (drs_.ingress.svc, svc_key), (drs_.egress.at, s_),
                   (drs_.egress.peer, d_), (drs_.egress.svc, svc_key)):
        idx = m._searchsorted_right(tab.bounds, x)
        tot = tot + tab.inc[idx].sum()
    return (acc.at[:1].add(tot.astype(jnp.int32)), drs_, s_, d_, p_, dp_)
if 3 in CASES:
    t_g = timeit("searchsorted+gathers+reduce (no consumer)", body_g, carry)

# 4) AND-in-XLA + 2-input pallas consumer
if 4 in CASES:
    from jax.experimental import pallas as pl

    @lru_cache(maxsize=4)
    def consumer2(b, w_in, w_out, in_phases, out_phases):
        def kernel(mi, mo, o_ref):
            i0, ik, ib = m._phase_scan_tile(mi[:], w_in, in_phases)
            o0, ok_, ob = m._phase_scan_tile(mo[:], w_out, out_phases)
            o_ref[:] = jnp.stack([i0, ik, ib, o0, ok_, ob,
                                  jnp.zeros_like(i0), jnp.zeros_like(i0)], axis=1)
        tb = m._FUSE_TB
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((b, 8), jnp.int32),
            grid=(b // tb,),
            in_specs=[pl.BlockSpec((tb, w), lambda i: (i, 0)) for w in (w_in, w_out)],
            out_specs=pl.BlockSpec((tb, 8), lambda i: (i, 0)),
            interpret=m.pallas_interpret(meta),
        )

    def body_and(i, carry):
        acc, drs_, s_, d_, p_, dp_ = carry
        dp2 = perturb(dp_, acc)
        svc_key = (p_ << 16) | dp2
        ing, egs = drs_.ingress, drs_.egress
        mi = (ing.at.inc[m._searchsorted_right(ing.at.bounds, d_)]
              & ing.peer.inc[m._searchsorted_right(ing.peer.bounds, s_)]
              & ing.svc.inc[m._searchsorted_right(ing.svc.bounds, svc_key)])
        mo = (egs.at.inc[m._searchsorted_right(egs.at.bounds, s_)]
              & egs.peer.inc[m._searchsorted_right(egs.peer.bounds, d_)]
              & egs.svc.inc[m._searchsorted_right(egs.svc.bounds, svc_key)])
        hits = consumer2(B, meta.w_in, meta.w_out, meta.in_phases, meta.out_phases)(
            mi.astype(jnp.int32), mo.astype(jnp.int32))
        return (acc.at[:1].add(hits[:, 0].sum()), drs_, s_, d_, p_, dp_)
    t_and = timeit("AND-in-XLA + 2-input consumer", body_and, carry)

# 5) round-6 overlap decomposition: churn-step cadences over the SAME
# rule world (empty service tables — the overlap under study is the
# drain/commit pipeline, not ServiceLB).  B-lane hot set, n_new fresh
# lanes per step from a one-per-flow pool; the drain runs as ONE
# coalesced round at miss_chunk == n_new with drain_reclaim=True.
if 5 in CASES:
    from antrea_tpu.compiler.services import compile_services
    from antrea_tpu.models import pipeline as pmod

    N_NEW = B // 8
    POOL = 1 << (12 if SMOKE else 18)
    pool_tr = gen_traffic(cluster.pod_ips, POOL, n_flows=POOL, seed=7,
                          one_per_flow=True)
    p_src = jnp.asarray(iputil.flip_u32(pool_tr.src_ip))
    p_dst = jnp.asarray(iputil.flip_u32(pool_tr.dst_ip))
    p_pro = jnp.asarray(pool_tr.proto)
    p_sp = jnp.asarray(pool_tr.src_port)
    p_dp = jnp.asarray(pool_tr.dst_port)
    pool_cols = (p_src, p_dst, p_pro, p_sp, p_dp)
    hot_cols = (src, dst, proto, jnp.asarray(tr.src_port), dport)

    step5, state5, (drs5, dsvc5) = pmod.make_pipeline(
        cps, compile_services([]), flow_slots=1 << (14 if SMOKE else 20),
        miss_chunk=N_NEW, fused=FUSED,
    )
    meta_fast = step5.meta._replace(phases=0)
    meta_drain = step5.meta._replace(drain_reclaim=True)
    for w in (100, 101):  # warm the hot set
        state5, _ = step5(state5, drs5, dsvc5, *hot_cols,
                          jnp.int32(w), jnp.int32(0))

    def overlap_body(fast, drain, deferred):
        """One churn iteration: optional fast step over the mixed batch,
        optional drain of the current (deferred=False) or previous
        (deferred=True) fresh window."""

        def body(i, carry):
            acc, st, drs_, dsvc_, hcols, pcols = carry
            off = (acc[1] * N_NEW) % (POOL - N_NEW)
            off_p = (jnp.maximum(acc[1] - 1, 0) * N_NEW) % (POOL - N_NEW)
            fresh = tuple(jax.lax.dynamic_slice(c, (off,), (N_NEW,))
                          for c in pcols)
            dwin = (tuple(jax.lax.dynamic_slice(c, (off_p,), (N_NEW,))
                          for c in pcols) if deferred else fresh)
            if fast:
                cols = tuple(jnp.concatenate([h[: B - N_NEW], f])
                             for h, f in zip(hcols, fresh))
                st, o = pmod._pipeline_step(st, drs_, dsvc_, *cols, 102 + i, 0,
                                            meta=meta_fast)
                acc = acc.at[0].add(o["code"].sum(dtype=jnp.int32) + o["n_miss"])
            if drain:
                st, od = pmod._pipeline_step(st, drs_, dsvc_, *dwin, 102 + i, 0,
                                             meta=meta_drain)
                acc = acc.at[0].add(od["code"].sum(dtype=jnp.int32)
                                    + od["n_miss"])
            acc = acc.at[1].add(1)
            return (acc, st, drs_, dsvc_, hcols, pcols)

        return body

    carry5 = (jnp.zeros(8, jnp.int32), state5, drs5, dsvc5, hot_cols, pool_cols)
    t_fast = timeit("churn fast step alone (phases=0)",
                    overlap_body(True, False, False), carry5)
    t_drain = timeit("coalesced drain alone (drain_reclaim)",
                     overlap_body(False, True, False), carry5)
    t_serial = timeit("fast + drain SERIALIZED (same window)",
                      overlap_body(True, True, False), carry5)
    t_ovl = timeit("fast + drain OVERLAPPED (window i-1 deferred)",
                   overlap_body(True, True, True), carry5)
    print(f"overlap decomposition: fast {t_fast*1e3:.2f} + drain "
          f"{t_drain*1e3:.2f} = {1e3*(t_fast+t_drain):.2f} ms predicted; "
          f"serialized {t_serial*1e3:.2f} ms, overlapped {t_ovl*1e3:.2f} ms "
          f"-> recovered {1e3*(t_serial-t_ovl):.2f} ms/step "
          f"({B/t_ovl/1e6:.2f}M pps overlapped)", flush=True)

# 6) round-7 pruning decomposition (the two-level aggregated-bitmap
# kernel): summary-only / pruned end-to-end per K / unpruned reference,
# fallback-rate-vs-K over PRUNE_LADDER, and a match-density sweep.
if 6 in CASES:
    drs_p, meta_p1 = m.to_device(cps, prune_budget=m.PRUNE_LADDER[0])
    S_in = int(drs_p.ingress.at.agg.shape[1])
    print(f"prune tables: w_in {meta_p1.w_in} (agg-padded), "
          f"S {S_in} superblocks", flush=True)

    def body_prune(meta_k, summary):
        def body(i, carry):
            acc, drs_, s_, d_, p_, dp_ = carry
            cls = m.classify_batch(
                drs_, s_, d_, p_, perturb(dp_, acc), meta=meta_k,
                fused=FUSED and not summary, summary_only=summary,
            )
            return (acc.at[:1].add(cls["code"].sum(dtype=jnp.int32)),
                    drs_, s_, d_, p_, dp_)
        return body

    carry6 = (jnp.zeros(8, jnp.int32), drs_p, src, dst, proto, dport)
    if t_full is None:
        t_full = timeit("end-to-end (unpruned reference)", body_full, carry)
    t_sum = timeit("summary-only (phase 1: agg gather + AND)",
                   body_prune(meta_p1, True), carry6)

    k_sweep = {}
    for k in m.PRUNE_LADDER:
        meta_k = meta_p1._replace(prune_budget=k)
        t_k = timeit(f"pruned end-to-end K={k}", body_prune(meta_k, False),
                     carry6)
        cls = m.classify_batch(drs_p, src, dst, proto, dport, meta=meta_k)
        k_sweep[str(k)] = {
            "pruned_s_per_batch": t_k,
            "pruned_pps": B / t_k,
            "fallback_rate": float(np.asarray(cls["prune_fb"]).mean()),
            "skip_rate": float(np.asarray(cls["prune_skip"]).mean()),
        }
        print(f"  K={k}: fb_rate {k_sweep[str(k)]['fallback_rate']:.4f} "
              f"skip_rate {k_sweep[str(k)]['skip_rate']:.4f}", flush=True)

    # Match-density sweep: replace a fraction of lanes with non-pod
    # (universe-external) endpoints so the aggregate AND proves no-match
    # — the default-deny / attack-traffic shape the short circuit targets.
    rng = np.random.default_rng(11)
    ext = rng.integers(1, 1 << 24, size=B).astype(np.uint32)  # 0.x.y.z: no pods
    meta_k4 = meta_p1._replace(prune_budget=4)
    density_sweep = {}
    for frac in (0.0, 0.5, 1.0):
        n_ext = int(B * frac)
        d_mix = np.asarray(tr.dst_ip).copy()
        s_mix = np.asarray(tr.src_ip).copy()
        d_mix[:n_ext] = ext[:n_ext]
        s_mix[:n_ext] = ext[::-1][:n_ext]
        cm = (jnp.zeros(8, jnp.int32), drs_p,
              jnp.asarray(iputil.flip_u32(s_mix)),
              jnp.asarray(iputil.flip_u32(d_mix)), proto, dport)
        t_d = timeit(f"pruned K=4, external-lane frac {frac}",
                     body_prune(meta_k4, False), cm)
        cls = m.classify_batch(cm[1], cm[2], cm[3], proto, dport,
                               meta=meta_k4)
        density_sweep[str(frac)] = {
            "pruned_pps": B / t_d,
            "skip_rate": float(np.asarray(cls["prune_skip"]).mean()),
            "fallback_rate": float(np.asarray(cls["prune_fb"]).mean()),
        }

    doc = {
        "metric": "cold_prune_decomposition",
        "smoke": SMOKE,
        "batch": B,
        "n_rules": N_RULES,
        "superblocks": S_in,
        "fused": FUSED,
        "unpruned_s_per_batch": t_full,
        "unpruned_pps": B / t_full,
        "summary_only_s_per_batch": t_sum,
        "summary_only_pps": B / t_sum,
        "k_sweep": k_sweep,
        "density_sweep": density_sweep,
    }
    line = json.dumps(doc)
    print(line, flush=True)
    with open(args.json, "w") as f:
        f.write(line + "\n")
    print(f"# wrote {args.json}", flush=True)
