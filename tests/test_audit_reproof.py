"""The audit re-proof owns its probe shape (datapath/tpuflow
._audit_fresh_state): whatever the count of rows a scan hands it — the
denials of a window, split per home replica on a mesh — the EAGER fresh
walk sees a canonical lane count (a power of two, at least the audit
window), because eager jax compiles every one-op kernel of the walk anew
for a lane count it has not met.  Only the real lanes come back, equal
lane for lane to the walk of the same rows unpadded."""

import pytest

from antrea_tpu.datapath import TpuflowDatapath
from antrea_tpu.models import pipeline as pl

_PROBE_KW = dict(flow_slots=1 << 10, aff_slots=1 << 6, miss_chunk=64,
                 audit_window=8, canary_probes=0, flightrec_slots=0,
                 realization_slots=0)


@pytest.fixture(scope="module")
def probe_engines():
    """A one-chip and a (2 data x 1 rule) mesh engine over one world, each
    warmed with the same batch -> {kind: (engine, its live audit rows)}."""
    import jax

    from antrea_tpu.parallel import MeshDatapath
    from antrea_tpu.simulator import gen_cluster, gen_traffic

    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    tr = gen_traffic(cluster.pod_ips, 256, n_flows=128, seed=3)
    out = {}
    one = TpuflowDatapath(cluster.ps, **_PROBE_KW)
    mesh = MeshDatapath(cluster.ps, n_data=2, n_rule=1,
                        devices=jax.devices("cpu")[:2], **_PROBE_KW)
    for kind, dp in (("tpuflow", one), ("mesh", mesh)):
        dp.step(tr, now=10)
        rows = dp._audit_window(0, dp._audit_slots(), 11)
        assert len(rows) >= 32, kind
        out[kind] = (dp, rows)
    return out


def _walk_lanes(monkeypatch):
    """Record the lane count of every eager `_pipeline_trace` call."""
    lanes = []
    orig = pl._pipeline_trace

    def recorded(state, drs, dsvc, src_f, *a, **k):
        lanes.append(int(src_f.shape[0]))
        return orig(state, drs, dsvc, src_f, *a, **k)

    monkeypatch.setattr(pl, "_pipeline_trace", recorded)
    return lanes


@pytest.mark.parametrize("n,want", [(3, 8), (5, 8), (7, 8), (9, 16)])
def test_audit_reproof_walks_canonical_lane_counts(probe_engines,
                                                   monkeypatch, n, want):
    """Catches a re-proof that walks len(rows) lanes: every new count of
    denials would recompile the eager walk's one-op kernels."""
    dp, rows = probe_engines["tpuflow"]
    unpadded = dp._audit_fresh_state(dp._state, rows[:16], 11)
    lanes = _walk_lanes(monkeypatch)
    got = dp._audit_fresh_state(dp._state, rows[:n], 11)
    assert lanes == [want]
    assert got == unpadded[:n]
    assert dp._audit_fresh_state(dp._state, [], 11) == [] and len(lanes) == 1


@pytest.mark.parametrize("counts,want", [((3, 5), [8, 8]), ((9, 2), [16, 8])])
def test_mesh_audit_reproof_pads_per_home_replica(probe_engines, monkeypatch,
                                                  counts, want):
    """Catches the mesh handing each replica's share to the walk unpadded
    (the split by home replica is what made the counts vary)."""
    dp, rows = probe_engines["mesh"]
    by_home = [[r for r in rows if r["slot"] % 2 == h] for h in (0, 1)]
    picked = by_home[0][:counts[0]] + by_home[1][:counts[1]]
    assert len(picked) == sum(counts)
    # Lane for lane the walk of each replica's 16 rows, none padded.
    truth = {r["slot"]: f for h in (0, 1) for r, f in zip(
        by_home[h][:16], dp._audit_fresh(by_home[h][:16], 11))}
    lanes = _walk_lanes(monkeypatch)
    got = dp._audit_fresh(picked, 11)
    assert lanes == want
    assert got == [truth[r["slot"]] for r in picked]
