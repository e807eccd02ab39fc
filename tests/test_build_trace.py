"""The XLA build ledger (observability/tracing.BuildLedger): every executable
the process builds is a row filed under the span that caused it, and the
step record carries the builds of its own call.

What is held:
  * a jitted function's first call adds one row with its `fun_name`,
    positive trace / lower / backend ns and the open span (a step, by its
    `seq`); a second call adds none, a new shape adds one;
  * an engine's first step at a new batch size records `xla_builds` >= 1,
    the next step at that size 0, and `build_trace()` holds the rows;
  * two engines in one process share the ledger, whose listeners are
    registered once;
  * the ring drops oldest and counts the drops; nested traces count once,
    a lowering no compile followed is dropped, a cache hit is marked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from antrea_tpu.datapath import TpuflowDatapath
from antrea_tpu.observability import tracing
from antrea_tpu.observability.tracing import (BUILD_CACHE, BuildLedger,
                                              StepTracer, build_ledger)
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic

KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=64)


def _rows_after(led, seq):
    return led.trace(since=seq)["records"]


def test_a_first_call_builds_one_row_under_the_open_step():
    led = build_ledger()

    @jax.jit
    def f(x):
        return jnp.sin(x) * 3 + 1

    st = StepTracer()
    seq0 = led.builds
    st.begin(4)
    f(np.ones(4, np.float32))
    st.end()
    rows = _rows_after(led, seq0)
    assert len(rows) == 1
    (row,) = rows
    assert row["fun_name"] == "jit(f)"
    assert row["trace_ns"] > 0 and row["lower_ns"] > 0
    assert row["backend_ns"] > 0
    assert (row["span"], row["step_seq"]) == ("step", st.steps_total)
    assert BUILD_CACHE[row["cache"]] in BUILD_CACHE
    rec = st.records()[-1]
    assert rec["xla_builds"] == 1
    assert rec["xla_build_ns"] == (row["trace_ns"] + row["lower_ns"]
                                   + row["backend_ns"])
    # an in-memory hit builds nothing, a new shape builds again
    seq1 = led.builds
    f(np.ones(4, np.float32))
    assert len(_rows_after(led, seq1)) == 0
    f(np.ones(5, np.float32))
    (again,) = _rows_after(led, seq1)
    assert again["fun_name"] == "jit(f)" and again["span"] == "other"
    assert again["step_seq"] == 0


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    return cluster, gen_services(8, cluster.pod_ips, seed=2)


def test_a_new_batch_size_builds_in_its_first_step_only(world):
    cluster, services = world
    dp = TpuflowDatapath(cluster.ps, services, **KW)
    # what construction built, if anything was not in memory yet
    first = dp.build_trace()
    assert first["dropped"] == 0
    assert set(first["records"]["span"]) <= {"construct", "other"}
    # a batch size no other test of this file steps
    batch = gen_traffic(cluster.pod_ips, 203, n_flows=50, seed=3)
    dp.step(batch, now=1)
    dp.step(batch, now=2)
    rec = dp.step_trace()["records"]
    assert rec["xla_builds"][0] >= 1 and rec["xla_build_ns"][0] > 0
    assert rec["xla_builds"][1] == 0 and rec["xla_build_ns"][1] == 0
    rows = dp.build_trace()["records"]
    in_step = rows[rows["span"] == "step"]
    assert len(in_step) == rec["xla_builds"][0]
    assert set(in_step["step_seq"]) == {rec["seq"][0]}
    assert (np.diff(rows["seq"]) > 0).all()


def test_two_engines_register_one_listener(world):
    cluster, services = world
    a = TpuflowDatapath(cluster.ps, services, **KW)
    b = TpuflowDatapath(**KW)
    led = build_ledger()
    assert a._steptrace._ledger is b._steptrace._ledger is led
    assert b._builds_from >= a._builds_from  # rows since each began
    durations = monitoring.get_event_duration_listeners()
    events = monitoring.get_event_listeners()
    assert sum(cb == led.on_duration for cb in durations) == 1
    assert sum(cb == led.on_event for cb in events) == 1


def _build(led, fun, trace_ms=(1.0,), lower_ms=2.0, backend_ms=3.0,
           hit=None):
    for ms in trace_ms:
        led.on_duration(tracing._EV_TRACE, ms / 1e3, fun_name=fun)
    led.on_duration(tracing._EV_LOWER, lower_ms / 1e3, fun_name=f"jit({fun})")
    if hit is not None:
        led.on_event(f"/jax/compilation_cache/cache_{hit}")
    led.on_duration(tracing._EV_BACKEND, backend_ms / 1e3,
                    fun_name=f"jit({fun})")


def test_the_ring_drops_oldest_and_counts_the_drops():
    led = BuildLedger(slots=3)
    for i in range(5):
        _build(led, f"f{i}")
    out = led.trace()
    assert list(out["records"]["seq"]) == [3, 4, 5]
    assert out["dropped"] == 2 and led.dropped == 2
    assert list(out["records"]["fun_name"]) == ["jit(f2)", "jit(f3)",
                                                "jit(f4)"]
    assert led.trace(since=1)["dropped"] == 1
    assert led.trace(since=4)["dropped"] == 0
    assert len(led.trace(since=4)["records"]) == 1
    assert led.builds == 5 and led.build_ns == 5 * 6_000_000


def test_nested_traces_count_once_and_a_bare_lowering_is_dropped():
    led = BuildLedger()
    # the traces of what f traced end first and lie inside f's own
    _build(led, "f", trace_ms=(0.0, 0.0, 1.0))
    assert led.trace()["records"]["trace_ns"][0] == 1_000_000
    # jit(g).lower() with no compile: its trace and lowering are dropped
    led.on_duration(tracing._EV_TRACE, 0.004, fun_name="g")
    led.on_duration(tracing._EV_LOWER, 0.004, fun_name="jit(g)")
    _build(led, "h", hit="hits")
    _build(led, "k", hit="misses")
    rows = led.trace()["records"]
    assert list(rows["fun_name"]) == ["jit(f)", "jit(h)", "jit(k)"]
    assert rows["lower_ns"][1] == 2_000_000
    assert [BUILD_CACHE[c] for c in rows["cache"]] == ["none", "hit", "miss"]
    assert led.build_ns == int(rows["trace_ns"].sum() + rows["lower_ns"].sum()
                               + rows["backend_ns"].sum())
