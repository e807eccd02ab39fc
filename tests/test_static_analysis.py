"""Unified static-analysis plane (ISSUE 14 tentpole): one AST engine
(antrea_tpu/analysis/), the nine migrated drift gates, the semantic
passes, and the baseline discipline.

Tier-1 invokes the FULL pass suite exactly ONCE here — the nine
scattered per-test subprocess invocations (test_profile/test_selfheal/
test_mesh_datapath/...) were retired with the migration; the legacy
tools/check_*.py CLIs remain as thin shims whose verdict parity with
the pass-based engine is pinned below, clean tree AND synthetically
broken tree per tool.

Each of the semantic passes additionally proves it FIRES on a
seeded violation (a minimal synthetic tree carrying exactly the bug
class the pass pins), so a future refactor that silently lobotomizes a
pass fails here, not in review."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from antrea_tpu.analysis import PASSES, run  # noqa: E402

ALL_PASSES = (
    "mesh", "metrics", "events", "commit-plane", "audit-plane",
    "maintenance", "reshard", "tenant",
    "thread-safety", "bounded-cache", "jit-purity", "donation-safety",
    "bounded-buffer", "telemetry-registry", "canonical-shape",
)


def _shim(tool: str, root: Path) -> int:
    """Run a legacy tools/check_*.py CLI shim against `root` -> exit."""
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / f"{tool}.py"),
         "--root", str(root)],
        capture_output=True, text=True).returncode


# ---------------------------------------------------------------------------
# The ONE tier-1 invocation of the whole suite (acceptance: analyze.py
# exits 0 on HEAD; all passes registered; --json machine-readable).
# ---------------------------------------------------------------------------

def test_full_suite_clean_on_head_one_invocation():
    assert tuple(PASSES) == ALL_PASSES
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "analyze.py"), "--json"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["clean"] is True
    assert tuple(report["passes"]) == ALL_PASSES
    # Machine-readable rows: every finding (there are none unsuppressed
    # on HEAD) carries pass/path/line/obj/reason/key/suppressed.
    for row in report["findings"]:
        assert set(row) == {"pass", "path", "line", "obj", "reason", "key",
                            "suppressed"}
        assert row["suppressed"] is True


# ---------------------------------------------------------------------------
# Migration parity: per legacy tool, the shim CLI's exit code matches
# the engine pass verdict on the clean tree AND on a synthetically
# broken one.
# ---------------------------------------------------------------------------

def _mutate_mesh(t: Path):
    p = t / "antrea_tpu" / "models" / "pipeline.py"
    txt = p.read_text()
    new = txt.replace("class FlowCache(NamedTuple):\n",
                      "class FlowCache(NamedTuple):\n"
                      "    bogus_unspecced_field: int\n", 1)
    assert new != txt
    p.write_text(new)


def _mutate_metrics(t: Path):
    p = t / "antrea_tpu" / "observability" / "flowexport.py"
    p.write_text(p.read_text()
                 + '\n_SEEDED = "antrea_tpu_bogus_unregistered_total"\n')


def _mutate_events(t: Path):
    p = t / "antrea_tpu" / "observability" / "flightrec.py"
    p.write_text(p.read_text()
                 + '\n\ndef _seeded_violation(rec):\n'
                   '    rec.emit(kind="not-a-declared-kind")\n')


def _mutate_commit(t: Path):
    p = t / "antrea_tpu" / "datapath" / "tpuflow.py"
    p.write_text(p.read_text()
                 + "\n\ndef install_bundle(self):\n    pass\n")


def _mutate_audit(t: Path):
    p = t / "antrea_tpu" / "datapath" / "audit.py"
    txt = p.read_text()
    new = txt.replace('"drs": "rule",', '"drs": "bogus",', 1)
    assert new != txt
    p.write_text(new)


def _mutate_maintenance(t: Path):
    p = t / "antrea_tpu" / "datapath" / "audit.py"
    p.write_text(p.read_text()
                 + "\n\ndef _rogue_loop(dp):\n"
                   "    return dp.canary_scan(0)\n")


def _mutate_reshard(t: Path):
    p = t / "antrea_tpu" / "parallel" / "reshard.py"
    txt = p.read_text()
    new = txt.replace('"FlowCache.keys"', '"BogusCache.keys"', 1)
    assert new != txt
    p.write_text(new)


def _mutate_tenant(t: Path):
    p = t / "antrea_tpu" / "datapath" / "tenancy.py"
    p.write_text(p.read_text()
                 + "\n\ndef _rogue_shard(mesh, tuples):\n"
                   "    return mesh.shard_of_tuples(tuples)\n")


LEGACY = [
    ("check_mesh", "mesh", _mutate_mesh),
    ("check_metrics", "metrics", _mutate_metrics),
    ("check_events", "events", _mutate_events),
    ("check_commit_plane", "commit-plane", _mutate_commit),
    ("check_audit_plane", "audit-plane", _mutate_audit),
    ("check_maintenance", "maintenance", _mutate_maintenance),
    ("check_reshard", "reshard", _mutate_reshard),
    ("check_tenant", "tenant", _mutate_tenant),
]


@pytest.fixture(scope="module")
def tree_template(tmp_path_factory):
    """A copy of everything the passes read: the package sources plus
    the repo-root surfaces (README, baseline)."""
    base = tmp_path_factory.mktemp("analysis") / "template"
    (base / "antrea_tpu").mkdir(parents=True)
    for src in (REPO / "antrea_tpu").rglob("*.py"):
        rel = src.relative_to(REPO)
        dst = base / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    for name in ("README.md", "BASELINE.analysis.json"):
        shutil.copy(REPO / name, base / name)
    return base


@pytest.mark.parametrize("tool,pass_id,mutate",
                         LEGACY, ids=[t for t, _p, _m in LEGACY])
def test_legacy_tool_verdict_parity(tool, pass_id, mutate, tree_template,
                                    tmp_path):
    # Clean tree: both verdicts green.
    clean = run(tree_template, [pass_id])
    assert clean.clean, [f.render() for f in clean.findings] + clean.errors
    assert _shim(tool, tree_template) == 0
    # Synthetically broken tree: both verdicts red.
    broken = tmp_path / "broken"
    shutil.copytree(tree_template, broken)
    mutate(broken)
    res = run(broken, [pass_id])
    assert not res.clean, f"{pass_id} missed the seeded breakage"
    assert _shim(tool, broken) == 1


# ---------------------------------------------------------------------------
# Seeded violations: each NEW semantic pass fires on the bug class it
# pins (and stays quiet on the adjacent legal shape).
# ---------------------------------------------------------------------------

def _mini_tree(tmp_path: Path, files: dict) -> Path:
    root = tmp_path / "mini"
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return root


def test_thread_safety_pass_fires_on_seeded_violations(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/agent/apiserver.py": (
            'HANDLER_SAFE = ("good_stats", "tickle")\n\n\n'
            "class AgentApiServer:\n"
            "    def _json_route(self, route, q):\n"
            "        self._dp.good_stats()\n"
            "        return self._dp.evil_poke()\n"
        ),
        "antrea_tpu/datapath/fake.py": (
            "class FakeDp:\n"
            "    def good_stats(self):\n"
            "        with self._world_ctx(1):\n"
            "            return {}\n\n"
            "    def tickle(self):\n"
            "        self.hits = 1\n"
            "        return 0\n\n"
            "    def unrelated(self):\n"
            "        self.fine = 2  # not handler-declared: no finding\n"
        ),
    })
    objs = {f.obj for f in run(root, ["thread-safety"]).findings}
    assert "undeclared:evil_poke" in objs
    assert "world-ctx:FakeDp.good_stats" in objs
    assert "mutates:FakeDp.tickle:hits" in objs
    assert not any("unrelated" in o for o in objs)


def test_bounded_cache_pass_fires_on_seeded_violations(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/x.py": (
            "from functools import cache, lru_cache\n"
            "import jax\n\n\n"
            "@lru_cache(maxsize=None)\n"
            "def leaky(n):\n"
            "    return jax.jit(lambda x: x + n)\n\n\n"
            "@cache\n"
            "def leaky2():\n"
            "    return jax.jit(lambda x: x)\n\n\n"
            "@lru_cache\n"
            "def leaky3(n):\n"
            "    return jax.vmap(lambda x: x * n)\n\n\n"
            "@lru_cache(maxsize=32)\n"
            "def bounded(n):\n"
            "    return jax.jit(lambda x: x * n)\n\n\n"
            "@lru_cache(maxsize=None)\n"
            "def host_data(n):\n"
            "    return list(range(n))\n"
        ),
    })
    objs = {f.obj for f in run(root, ["bounded-cache"]).findings}
    assert objs == {"x.py:leaky", "x.py:leaky2", "x.py:leaky3"}


def test_canonical_shape_pass_fires_on_seeded_violations(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/datapath/bad.py": (
            "from .tenancy import _sub_batch\n\n\n"
            "class Dp:\n"
            "    def step_groups(self, tids, batch, now):\n"
            "        for tid in set(tids):\n"
            "            sub = _sub_batch(batch, [0])\n"
            "            self.step(sub, now)  # tainted name\n"
            "        return self.tenant_step(1, _sub_batch(batch, [1]),\n"
            "                                now)  # inline\n\n"
            "    def staged(self, batch, now):\n"
            "        # The sanctioned pattern: subsets go INTO the\n"
            "        # batcher, which dispatches canonical shapes.\n"
            "        t = self.batcher.submit(_sub_batch(batch, [0]), now)\n"
            "        self.batcher.flush_all(now)\n"
            "        return self.step(batch, now)\n"
        ),
    })
    objs = {f.obj for f in run(root, ["canonical-shape"]).findings}
    assert objs == {"datapath/bad.py:step_groups:step",
                    "datapath/bad.py:step_groups:tenant_step"}


def test_jit_purity_pass_fires_on_seeded_violations(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/y.py": (
            "import jax\n\n\n"
            "def _step(state, x, meta):\n"
            "    n = int(x)  # tracer coercion\n"
            "    return x\n\n\n"
            "step = jax.jit(_step, static_argnames=('meta',))\n\n\n"
            "def _ok(a, meta):\n"
            "    k = int(meta.chunk)  # static arg: exempt\n"
            "    return a\n\n\n"
            "ok = jax.jit(_ok, static_argnames=('meta',))\n\n\n"
            "def _sync(a):\n"
            "    return a.sum().item()\n\n\n"
            "sync = jax.jit(_sync)\n\n\n"
            "class C:\n"
            "    @jax.jit\n"
            "    def m(self, x):\n"
            "        self.cached = x\n"
            "        return x\n\n\n"
            "def host(a):\n"
            "    return int(a)  # not jitted: no finding\n"
        ),
    })
    objs = {f.obj for f in run(root, ["jit-purity"]).findings}
    assert any(o.startswith("y.py:_step:int") for o in objs)
    assert any(o.startswith("y.py:_sync:item") for o in objs)
    assert "y.py:m:self.cached" in objs
    assert not any("_ok" in o or "host" in o for o in objs)


def test_donation_safety_pass_fires_on_seeded_violation(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/z.py": (
            "import jax\n\n\n"
            "def _f(s, x):\n"
            "    return s\n\n\n"
            "f_don = jax.jit(_f, donate_argnums=(0,))\n\n\n"
            "class Eng:\n"
            "    def caller_bad(self):\n"
            "        out = f_don(self._state, 1)\n"
            "        return self._state.sum()  # read of donated buffers\n\n"
            "    def caller_ok(self):\n"
            "        out = f_don(self._state, 1)\n"
            "        self._state = out  # rebind kills the taint\n"
            "        return self._state.sum()\n\n"
            "    def caller_alias(self):\n"
            "        fn = f_don if True else _f\n"
            "        out = fn(self._state, 1)\n"
            "        return self._state.sum()  # alias tracked too\n\n"
            "    def caller_loop_bad(self, blocks):\n"
            "        acc = 0\n"
            "        for b in blocks:\n"
            "            acc += self._state.rows  # rereads next iter\n"
            "            out = f_don(self._state, b)\n"
            "        return acc\n\n"
            "    def caller_loop_ok(self, blocks):\n"
            "        for b in blocks:\n"
            "            out = f_don(self._state, b)\n"
            "            self._state = out  # rebind each iteration\n"
            "        return self._state.rows\n\n"
            "    def caller_same_line(self):\n"
            "        return f_don(self._state, 1), self._state.rows\n"
        ),
    })
    objs = {f.obj for f in run(root, ["donation-safety"]).findings}
    assert any(o.startswith("z.py:caller_bad:self._state") for o in objs)
    assert any(o.startswith("z.py:caller_alias:self._state") for o in objs)
    # Execution-order discipline: a dispatch inside a loop wraps around
    # (the body's earlier read runs again AFTER it), a same-iteration
    # rebind kills the taint, and a same-LINE read after the call counts.
    assert "z.py:caller_loop_bad:self._state" in objs
    assert "z.py:caller_same_line:self._state" in objs
    assert not any("caller_loop_ok" in o for o in objs)
    assert not any("caller_ok" in o for o in objs)


def test_bounded_buffer_pass_fires_on_seeded_violations(tmp_path):
    root = _mini_tree(tmp_path, {
        "antrea_tpu/dissemination/wild.py": (
            "from collections import deque\n\n"
            'BUFFER_CAPS = {\n'
            '    "W.good_queue": "bounded at max_pending",\n'
            '    "W.ghost_buf": "names a buffer nobody assigns",\n'
            "}\n\n\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self.good_queue = deque()  # declared: no finding\n"
            "        self.evil_backlog = []  # undeclared buffer\n"
            "        self._rdbuf: bytes = b''  # AnnAssign form, undeclared\n"
            "        self.count = 0  # not buffer-shaped: no finding\n"
        ),
        # Buffers OUTSIDE dissemination/ are out of scope for this pass.
        "antrea_tpu/datapath/elsewhere.py": (
            "class E:\n"
            "    def __init__(self):\n"
            "        self.free_queue = []\n"
        ),
        # The failover plane is a SINGLE-FILE scan entry (its probe-
        # history ring sits between an every-tick producer and a
        # maybe-never supportbundle consumer): a declared ring passes,
        # an undeclared buffer beside it fires.
        "antrea_tpu/parallel/failover.py": (
            "from collections import deque\n\n"
            'BUFFER_CAPS = {\n'
            '    "FailoverPlane.probe_ring": "deque(maxlen=PROBE_RING)",\n'
            "}\n\n\n"
            "class FailoverPlane:\n"
            "    def __init__(self):\n"
            "        self.probe_ring = deque(maxlen=64)\n"
            "        self.sneaky_backlog = []  # undeclared buffer\n"
        ),
        # Sibling parallel/ modules stay OUT of scope: the entry names
        # one file, not the package.
        "antrea_tpu/parallel/meshpath.py": (
            "class M:\n"
            "    def __init__(self):\n"
            "        self.replica_queue = []\n"
        ),
    })
    objs = {f.obj for f in run(root, ["bounded-buffer"]).findings}
    assert "dissemination/wild.py:W.evil_backlog" in objs
    assert "dissemination/wild.py:W._rdbuf" in objs
    # Stale declarations are findings too: a cap row cannot outlive the
    # buffer it excuses.
    assert "dissemination/wild.py:W.ghost_buf:stale" in objs
    assert "parallel/failover.py:FailoverPlane.sneaky_backlog" in objs
    assert not any("good_queue" in o for o in objs)
    assert not any("probe_ring" in o for o in objs)
    assert not any("count" in o for o in objs)
    assert not any("elsewhere" in o for o in objs)
    assert not any("meshpath" in o for o in objs)


def test_telemetry_registry_pass_fires_on_seeded_violations(tree_template,
                                                            tmp_path):
    # Clean on the real tree (the tier-1 full-suite test pins this too;
    # here it anchors the seeded deltas below).
    clean = run(tree_template, ["telemetry-registry"])
    assert clean.clean, [f.render() for f in clean.findings] + clean.errors

    # A kernel counter output nobody declared: the plane would silently
    # drop it on account().
    broken = tmp_path / "undeclared"
    shutil.copytree(tree_template, broken)
    p = broken / "antrea_tpu" / "models" / "pipeline.py"
    p.write_text(p.read_text()
                 + '\n\ndef _seeded(out):\n'
                   '    out["tel_bogus_counter"] = 0\n')
    objs = {f.obj for f in run(broken, ["telemetry-registry"]).findings}
    assert "undeclared:bogus_counter" in objs

    # A declared counter with no kernel emit site, no metric family row
    # and no README row: dead accumulator across every layer.
    broken2 = tmp_path / "unmeasured"
    shutil.copytree(tree_template, broken2)
    t = broken2 / "antrea_tpu" / "observability" / "telemetry.py"
    txt = t.read_text()
    new = txt.replace('    "dma_hb",', '    "dma_hb",\n    "ghost_total",', 1)
    assert new != txt
    t.write_text(new)
    objs2 = {f.obj for f in run(broken2, ["telemetry-registry"]).findings}
    assert {"unmeasured:ghost_total", "family-unmapped:ghost_total",
            "undocumented:ghost_total"} <= objs2

    # A regime dropped from the README table is drift, not a doc nit.
    broken3 = tmp_path / "undocumented-regime"
    shutil.copytree(tree_template, broken3)
    r = broken3 / "README.md"
    rt = r.read_text()
    new = rt.replace("| `attack-shed` |", "| attack shed |")
    assert new != rt
    r.write_text(new)
    objs3 = {f.obj for f in run(broken3, ["telemetry-registry"]).findings}
    assert "regime-undocumented:attack-shed" in objs3


def test_reshard_world_migration_fires_on_seeded_violation(tree_template,
                                                           tmp_path):
    """The PR 20 tenant extension of the reshard pass: a NEW
    _TENANT_WORLD_FIELDS member of the mesh engine assigned from a
    sharded-state builder but absent from reshard.WORLD_MIGRATION is
    flow loss for EVERY tenant at once — the pass must fire on it (and
    on a stale rule naming no such field), and stay clean at HEAD."""
    clean = run(tree_template, ["reshard"])
    assert clean.clean, [f.render() for f in clean.findings] + clean.errors

    # A sharded per-world field nobody taught the per-world migrator.
    broken = tmp_path / "unmigrated-world"
    shutil.copytree(tree_template, broken)
    p = broken / "antrea_tpu" / "parallel" / "meshpath.py"
    txt = p.read_text()
    new = txt.replace('        "_fo_mask",\n',
                      '        "_fo_mask", "_shadow_state",\n', 1)
    assert new != txt
    p.write_text(new + "\n\ndef _seeded(self, st):\n"
                       "    self._shadow_state = self._pin_state(st)\n")
    objs = {f.obj for f in run(broken, ["reshard"]).findings}
    assert "unmigrated-world:_shadow_state" in objs

    # A WORLD_MIGRATION rule whose field no longer exists: stale rule.
    broken2 = tmp_path / "stale-world"
    shutil.copytree(tree_template, broken2)
    r = broken2 / "antrea_tpu" / "parallel" / "reshard.py"
    txt = r.read_text()
    new = txt.replace('WORLD_MIGRATION = {\n',
                      'WORLD_MIGRATION = {\n'
                      '    "_ghost_state": "row-migrate a field that '
                      'no longer exists",\n', 1)
    assert new != txt
    r.write_text(new)
    objs2 = {f.obj for f in run(broken2, ["reshard"]).findings}
    assert "stale-world:_ghost_state" in objs2


# ---------------------------------------------------------------------------
# Baseline discipline: suppression works, staleness fails the build.
# ---------------------------------------------------------------------------

def _leaky_tree(tmp_path: Path) -> Path:
    return _mini_tree(tmp_path, {
        "antrea_tpu/x.py": (
            "from functools import lru_cache\n"
            "import jax\n\n\n"
            "@lru_cache(maxsize=None)\n"
            "def leaky(n):\n"
            "    return jax.jit(lambda x: x + n)\n"
        ),
    })


def test_baseline_suppresses_by_key_and_fails_when_stale(tmp_path):
    root = _leaky_tree(tmp_path)
    [finding] = run(root, ["bounded-cache"]).findings
    # A baselined finding is suppressed (run goes clean, row reported).
    (root / "BASELINE.analysis.json").write_text(json.dumps(
        {"findings": {finding.key: "known leak, tracked in ISSUE-XX"}}))
    res = run(root, ["bounded-cache"])
    assert res.clean and [s.key for s in res.suppressed] == [finding.key]
    # A stale row (nothing fires for it any more) fails the build.
    (root / "antrea_tpu" / "x.py").write_text("X = 1\n")
    res2 = run(root, ["bounded-cache"])
    assert not res2.clean
    assert any("stale" in e for e in res2.errors), res2.errors
    # A reasonless row is rejected outright.
    (root / "BASELINE.analysis.json").write_text(json.dumps(
        {"findings": {finding.key: ""}}))
    assert any("no reason" in e for e in run(root, ["bounded-cache"]).errors)


def test_runner_rejects_unknown_pass():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "analyze.py"),
         "--pass", "no-such-pass"],
        capture_output=True, text=True)
    assert proc.returncode == 2
    assert "no-such-pass" in proc.stderr


def test_every_pass_declares_an_invariant():
    for pid, (_fn, invariant) in PASSES.items():
        assert isinstance(invariant, str) and invariant.strip(), pid
    # Finding keys are stable identities: pass:path:obj.
    from antrea_tpu.analysis import Finding

    f = Finding("mesh", "a/b.py", 3, "why", obj="Cls.field")
    assert f.key == "mesh:a/b.py:Cls.field"
    assert re.match(r"DRIFT\[mesh\] a/b\.py:3: why", f.render())
