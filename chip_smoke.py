#!/usr/bin/env python
"""Chip smoke: serve the 100k-rule world on one TPU through the ordinary
engine entry points, and hold what comes out to the scalar oracle.

Not a benchmark — it prints no rate.  It is the quickest proof that the
system still starts on the chip: one process, JAX imported once, no child.

  device gate     jax.default_backend() must be "tpu", before anything is
                  built; otherwise exit non-zero, naming what was found.
  the deployment  BASELINE config 4 with config 3's service load, from
                  fixed seeds: 100k rules over 64x32 pods,
                  5k services, 2^22 flow slots, a 131,072-lane batch from a
                  32k-flow Zipf universe.  Nothing reduced.
  the entry       make_datapath("tpuflow", ...) then install_bundle(ps,
                  services): compile -> canary (64 probes held to Oracle,
                  on the chip) -> swap -> settle.
  verdicts        a seeded 512-lane batch stepped cold and warm, every
                  StepResult field compared with an OracleDatapath twin.
  full width      the 131,072-lane batch cold, warm, and with 1/8 of its
                  lanes replaced by unseen flows.
  every kernel    the same twin comparison plus one full-width cold step
                  on a fresh engine per Pallas knob set — never served
                  from the interpreter.
  nothing hidden  per engine: not degraded, no canary error / mismatch /
                  rollback in commit_stats() or the flight recorder, one
                  clean canary_scan, state and rules on the gated device,
                  interpret mode off.

It stops at the first thing that fails (SystemExit, non-zero).  The last
line of stdout is {"ok": true, "device": {...}} as JAX reports the device.

tests/test_chip_smoke.py rehearses these same functions on a tiny world
under the CPU backend, passing the gate's platform in as a parameter.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.metadata
import json
import sys
import time
import warnings
from typing import NamedTuple, Optional

import jax
import numpy as np

from antrea_tpu.datapath import OracleDatapath, make_datapath
from antrea_tpu.ops.match import pallas_interpret
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic
from antrea_tpu.utils.compile_cache import enable_compile_cache


class Sizes(NamedTuple):
    n_rules: int
    n_nodes: int
    pods_per_node: int
    n_services: int
    batch: int
    n_flows: int
    twin_lanes: int
    # Engine constructor sizes; the twin takes the table sizes too.
    flow_slots: int
    aff_slots: Optional[int] = None  # None = the engine's default
    miss_chunk: Optional[int] = None

    def table_kw(self) -> dict:
        kw = {"flow_slots": self.flow_slots}
        if self.aff_slots is not None:
            kw["aff_slots"] = self.aff_slots
        return kw

    def engine_kw(self) -> dict:
        kw = self.table_kw()
        if self.miss_chunk is not None:
            kw["miss_chunk"] = self.miss_chunk
        return kw


# BASELINE config 4 + config 3's services (the sizes of the np100k cells).
HEADLINE = Sizes(n_rules=100_000, n_nodes=64, pods_per_node=32,
                 n_services=5_000, batch=1 << 17, n_flows=1 << 15,
                 twin_lanes=512, flow_slots=1 << 22)

# name -> engine knobs.  "default" also serves the warm and churn steps.
ENGINES = (
    ("default", {}),
    ("fused", {"fused": True}),  # staged Pallas consumer
    ("pruned", {"prune_budget": 4}),  # aggregate prune, XLA scan
    # aggregate prune, Pallas consumer over the candidate matrices
    ("fused_pruned", {"fused": True, "prune_budget": 4}),
)

_COMPARED = ("code", "est", "reply", "reject_kind", "snat", "svc_idx",
             "dnat_ip", "dnat_port", "committed")


def require(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_gate(want: str = "tpu") -> dict:
    """Exit unless the default backend is `want`; -> the device as JAX
    reports it.  Runs before anything is built."""
    found = jax.default_backend()
    if found != want:
        raise SystemExit(
            f"chip_smoke: the default JAX backend is {found!r}, need "
            f"{want!r}; nothing was built")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}; " + " ".join(
        f"{dist} {importlib.metadata.version(dist)}"
        for dist in ("jax", "jaxlib", "libtpu")))
    return device


class World(NamedTuple):
    ps: object
    services: list
    twin_batch: PacketBatch
    full_batch: PacketBatch
    churn_batch: PacketBatch


def build_world(sz: Sizes) -> World:
    """Everything from seeds (cluster 1, services 2, traffic 3)."""
    cluster = gen_cluster(sz.n_rules, n_nodes=sz.n_nodes,
                          pods_per_node=sz.pods_per_node, seed=1)
    services = gen_services(sz.n_services, cluster.pod_ips, seed=2)
    kw = dict(services=services, svc_fraction=0.3)
    full = gen_traffic(cluster.pod_ips, sz.batch, n_flows=sz.n_flows,
                       seed=3, **kw)
    # A third as many flows as lanes: repeats inside the batch, so the
    # warm step sees both established hits and cached denials.
    twin = gen_traffic(cluster.pod_ips, sz.twin_lanes,
                       n_flows=max(8, sz.twin_lanes // 3), seed=4, **kw)
    n_new = sz.batch // 8
    fresh = gen_traffic(cluster.pod_ips, n_new, n_flows=n_new, seed=5, **kw)
    churn = PacketBatch(**{
        f: np.concatenate([getattr(fresh, f), getattr(full, f)[n_new:]])
        for f in ("src_ip", "dst_ip", "proto", "src_port", "dst_port")})
    return World(cluster.ps, services, twin, full, churn)


def mismatched_lanes(got, want) -> np.ndarray:
    """Lanes on which two StepResults disagree — the comparison
    tests/test_datapath.py's _diff makes: every verdict/NAT/conntrack
    column, and rule attribution on freshly classified denials (cached
    hits report at-commit attribution on both sides)."""
    bad = np.zeros(len(got.code), bool)
    for f in _COMPARED:
        bad |= np.asarray(getattr(got, f)) != np.asarray(getattr(want, f))
    fresh = ((np.asarray(got.est) == 0) & (np.asarray(got.committed) == 0)
             & (np.asarray(got.code) != 0))
    for f in ("ingress_rule", "egress_rule"):
        differ = np.array([a != b for a, b in
                           zip(getattr(got, f), getattr(want, f))])
        bad |= fresh & differ
    return np.nonzero(bad)[0]


def check_lanes(res, lanes: int, what: str) -> None:
    """Every per-lane StepResult field the engine filled has `lanes`."""
    for f in dataclasses.fields(res):
        v = getattr(res, f.name)
        if v is None or f.name == "n_miss":
            continue
        require(len(v) == lanes,
                f"{what}: StepResult.{f.name} has {len(v)} lanes, not {lanes}")


def check_nothing_swallowed(dp, name: str, want: str, now: int) -> None:
    """The engine's catch-and-degrade sites (canary watchdog, degraded
    recompile) would turn a compiler error into "degraded": read every
    place that would show it."""
    require(not dp.degraded, f"{name}: engine is degraded")
    cs = dp.commit_stats()
    bad = {k: n for k, n in cs["commits"].items()
           if n and k.split("/")[1] in ("error", "mismatch")}
    require(not bad and not cs["rollbacks_total"]
            and not cs["canary_mismatches_total"] and not cs["last_error"],
            f"{name}: commit plane recorded a fault: {cs}")
    for e in dp.flightrecorder_events():
        require(e["kind"] not in ("degrade", "rollback", "canary-mismatch")
                and not (e["kind"] == "commit"
                         and e.get("outcome") == "error"),
                f"{name}: flight recorder holds {e}")
    scan = dp.canary_scan(now)
    require(scan["probes"] > 0 and scan["mismatches"] == 0
            and not scan["degraded"], f"{name}: canary_scan -> {scan}")
    for what, leaf in (("state", dp._state.flow.keys),
                       ("rules", dp._drs.ingress.at.inc)):
        found = {d.platform for d in leaf.devices()}
        require(found == {want}, f"{name}: {what} arrays live on {found}")
    require(pallas_interpret(dp._meta.match) == (want != "tpu"),
            f"{name}: interpret mode is "
            f"{pallas_interpret(dp._meta.match)} on {want}")


def serve_engine(name: str, knobs: dict, world: World, sz: Sizes, *,
                 want: str, full_steps: bool) -> dict:
    """Build one engine through the plug-in boundary, install the world
    through the commit plane, serve, compare with the twin -> a report
    (lanes compared / mismatched, wall seconds per phase)."""
    t0 = time.perf_counter()
    dp = make_datapath("tpuflow", **sz.engine_kw(), **knobs)
    dp.install_bundle(world.ps, world.services)
    secs = {"install": time.perf_counter() - t0}

    # Verdicts: the twin validates the same knobs and ignores them.
    t0 = time.perf_counter()
    twin = OracleDatapath(world.ps, world.services, **sz.table_kw(), **knobs)
    want_cold = twin.step(world.twin_batch, 100)
    want_warm = twin.step(world.twin_batch, 101)
    secs["oracle_twin"] = time.perf_counter() - t0
    compared = mismatched = 0
    for label, now, want_res in (("twin_cold", 100, want_cold),
                                 ("twin_warm", 101, want_warm)):
        t0 = time.perf_counter()
        got = dp.step(world.twin_batch, now)
        secs[label] = time.perf_counter() - t0
        check_lanes(got, sz.twin_lanes, f"{name} {label}")
        require(got.n_miss == want_res.n_miss,
                f"{name} {label}: n_miss {got.n_miss} != twin's "
                f"{want_res.n_miss}")
        bad = mismatched_lanes(got, want_res)
        compared += sz.twin_lanes
        mismatched += len(bad)
        require(not len(bad),
                f"{name} {label}: {len(bad)} of {sz.twin_lanes} lanes "
                f"differ from the oracle twin, first lane {bad[:1]}")
    require(int(want_warm.est.sum()) > 0, f"{name}: warm twin step hit "
            f"no established flow — the sample proves nothing")

    # Full width.
    t0 = time.perf_counter()
    cold = dp.step(world.full_batch, 200)
    secs["full_cold"] = time.perf_counter() - t0
    check_lanes(cold, sz.batch, f"{name} full_cold")
    chunk = dp._meta.miss_chunk
    require(cold.n_miss > chunk, f"{name}: cold full-width step missed "
            f"{cold.n_miss} lanes, not more than one {chunk}-lane round")
    misses = {"full_cold": cold.n_miss}
    if full_steps:
        t0 = time.perf_counter()
        warm = dp.step(world.full_batch, 201)
        secs["full_warm"] = time.perf_counter() - t0
        check_lanes(warm, sz.batch, f"{name} full_warm")
        require(int(warm.est.sum()) > 0 and warm.n_miss < cold.n_miss,
                f"{name}: warm step est={int(warm.est.sum())} "
                f"n_miss={warm.n_miss} (cold {cold.n_miss})")
        before = dp.cache_stats()
        t0 = time.perf_counter()
        churn = dp.step(world.churn_batch, 202)
        secs["full_churn"] = time.perf_counter() - t0
        check_lanes(churn, sz.batch, f"{name} full_churn")
        after = dp.cache_stats()
        require(churn.n_miss > warm.n_miss, f"{name}: churn step missed "
                f"{churn.n_miss} lanes, warm {warm.n_miss}")
        # Each miss writes at most a forward and a reply row; what it
        # overwrote is either counted as an eviction or was dead.
        grew = after["occupied"] - before["occupied"]
        require(0 < after["occupied"] <= sz.flow_slots
                and grew <= 2 * churn.n_miss
                and after["evictions"] >= before["evictions"],
                f"{name}: cache census {before} -> {after} after "
                f"{churn.n_miss} misses")
        misses.update(full_warm=warm.n_miss, full_churn=churn.n_miss,
                      evictions=after["evictions"],
                      occupied=after["occupied"])

    t0 = time.perf_counter()
    check_nothing_swallowed(dp, name, want, now=300)
    secs["canary_scan"] = time.perf_counter() - t0
    mem = jax.devices()[0].memory_stats() or {}
    report = {
        "lanes_compared": compared, "lanes_mismatched": mismatched,
        "n_miss": misses,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use", "not reported"),
        "setup_seconds": {k: round(v, 2) for k, v in secs.items()},
    }
    if dp.prune_stats() is not None:
        report["prune"] = {k: v for k, v in dp.prune_stats().items()
                           if k.endswith("_total")}
    say(f"{name}: {json.dumps(report)}")
    return report


def run(sz: Sizes, want: str = "tpu", engines=ENGINES) -> dict:
    """The whole smoke at `sz` on backend `want` -> the last line's dict."""
    device = device_gate(want)
    # The smoke path must raise no DeprecationWarning from antrea_tpu/.
    warnings.filterwarnings("error", category=DeprecationWarning,
                            module=r"antrea_tpu(\.|$)")
    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    world = build_world(sz)
    say(f"world built in {time.perf_counter() - t0:.1f}s (set-up): "
        f"{sz.n_rules} rules asked, {sz.n_services} services, "
        f"batch {sz.batch}, flow_slots {sz.flow_slots}")
    reports = {}
    for name, knobs in engines:
        reports[name] = serve_engine(name, knobs, world, sz, want=want,
                                     full_steps=(name == "default"))
        gc.collect()  # the engine is dropped before the next is built
    say("engines: " + json.dumps(
        {n: "served" for n in reports} | {"claim": None}))
    return {"ok": True, "device": device}


def main() -> int:
    print(json.dumps(run(HEADLINE)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
