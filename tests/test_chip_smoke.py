"""Tier-1 rehearsal of chip_smoke.py: its own functions on a tiny world
under the CPU backend, with the device gate's platform passed in (the
script's default stays "TPU or fail").  The world and engine sizes are
tests/test_datapath.py's `_mk_pair` shapes; twin and full batch share one
lane count so each engine compiles one step shape."""

import copy

import pytest

import chip_smoke as cs

TINY = cs.Sizes(n_rules=120, n_nodes=4, pods_per_node=8, n_services=12,
                batch=192, n_flows=64, twin_lanes=192, flow_slots=1 << 12,
                aff_slots=1 << 10, miss_chunk=64)


def _engines(*names):
    return [e for e in cs.ENGINES if e[0] in names]


def test_rehearsal_default_and_staged_consumer(capsys):
    out = cs.run(TINY, want="cpu", engines=_engines("default", "fused"))
    assert out == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                          "count": out["device"]["count"]}}
    log = capsys.readouterr().out
    assert log.count('"lanes_compared": 384, "lanes_mismatched": 0') == 2
    assert '"claim": null' in log.splitlines()[-1]


def test_rehearsal_fused_pruned(capsys):
    """Catches `fused` + `prune_budget` building anything but the Pallas
    consumer over the candidate matrices, or that engine leaving the twin."""
    cs.run(TINY, want="cpu", engines=_engines("fused_pruned"))
    log = capsys.readouterr().out
    assert log.count('"lanes_compared": 384, "lanes_mismatched": 0') == 1
    assert '"fused_pruned": "served"' in log.splitlines()[-1]


@pytest.mark.slow
def test_rehearsal_pruned():
    cs.run(TINY, want="cpu", engines=_engines("pruned"))


def test_gate_names_the_backend_before_building(monkeypatch):
    monkeypatch.setattr(cs, "build_world", lambda sz: pytest.fail("built"))
    with pytest.raises(SystemExit, match="backend is 'cpu', need 'tpu'"):
        cs.run(TINY)


def test_comparison_finds_a_flipped_lane_and_a_misattributed_denial():
    world = cs.build_world(TINY)
    twin = cs.OracleDatapath(world.ps, world.services, **TINY.table_kw())
    want = twin.step(world.twin_batch, 100)
    got = copy.deepcopy(want)
    assert cs.mismatched_lanes(got, want).size == 0
    got.dnat_port[7] ^= 1
    denied = next(i for i in range(TINY.twin_lanes)
                  if want.code[i] != 0 and not want.committed[i])
    got.ingress_rule[denied] = "someone-else"
    assert cs.mismatched_lanes(got, want).tolist() == sorted({7, denied})
