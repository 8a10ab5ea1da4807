"""Machine-checked TPU invariants (ISSUE 14): the static-analysis gate.

Runs the three passes of :mod:`raft_tpu.analysis` against the live tree
under a zero-NEW-findings policy (``analysis/baseline.json``), keeps the
kernel registry honest with a source-grep drift guard (the
``guarded_call``/``POLICIES`` sweep pattern from test_quality.py), and
proves each rule class actually fires by injecting a violation of it
into a fixture kernel/module.

Everything here is AST- and trace-level only — no device work, no XLA
compiles — so the whole file stays tier-1 cheap (<5s; the one traced
fixture kernel runs in interpret shape-tracing only).
"""
import pathlib
import re

import pytest

from raft_tpu import analysis
from raft_tpu.analysis import hotpath_audit, kernel_audit, lock_lint

pytestmark = pytest.mark.analysis

ROOT = analysis.repo_root()


@pytest.fixture(scope="module")
def tree_run():
    """ONE full-tree run shared by the gate tests (the expensive part —
    ~19 kernel variant traces — happens once per module)."""
    reports = []
    findings = analysis.run_all(ROOT, kernel_reports=reports)
    return findings, reports


class TestBaselineGate:
    def test_zero_new_findings(self, tree_run):
        """THE gate: a new kernel, serving path, or thread that violates
        an invariant cannot merge without either fixing it, waiving it
        inline with a reason, or consciously baselining it."""
        findings, _ = tree_run
        verdict = analysis.compare(findings)
        by_key = {f.key: f for f in findings}
        new = "\n".join(f"  {by_key[k].render()}" for k in verdict["new"])
        assert not verdict["new"], (
            f"{len(verdict['new'])} NEW analysis findings (fix, waive "
            f"with '# lint: waive(<rule>): <reason>', or rebaseline via "
            f"scratch/run_analysis.py --update-baseline):\n{new}")

    def test_no_stale_baseline_entries(self, tree_run):
        """A fixed finding must leave the baseline (run
        ``scratch/run_analysis.py --update-baseline``) — a stale entry
        would silently re-admit a regression of the same key."""
        findings, _ = tree_run
        verdict = analysis.compare(findings)
        assert not verdict["stale"], (
            f"baseline entries no longer firing: {verdict['stale']}")

    def test_waivers_name_known_rules(self):
        """A typo'd waiver never fires; reject waivers naming unknown
        rules anywhere in the package."""
        bad = []
        waive_re = re.compile(r"#\s*lint:\s*waive\(([\w.-]+)\)")
        for p in (pathlib.Path(ROOT) / "raft_tpu").rglob("*.py"):
            for i, line in enumerate(p.read_text().splitlines(), 1):
                for m in waive_re.finditer(line):
                    if m.group(1) not in analysis.KNOWN_RULES:
                        bad.append(f"{p}:{i}: waive({m.group(1)})")
        assert not bad, f"waivers naming unknown rules: {bad}"

    def test_partial_rebaseline_preserves_other_passes(self):
        """`--update-baseline --passes lock` must merge into, never
        wipe, the kernel audit's baseline slice."""
        lock_only = [analysis.Finding("unlocked-attr", "a.py", "X.m.a",
                                      "msg", 3)]
        merged = analysis.merged_baseline_keys(lock_only,
                                               passes=("lock",))
        kernel_entries = [k for k in analysis.load_baseline()
                          if k.split("::")[0] not in
                          analysis.PASS_RULES["lock"]]
        assert set(kernel_entries) <= set(merged)
        assert "unlocked-attr::a.py::X.m.a" in merged
        # a full-pass rebaseline is exactly this run's findings
        assert analysis.merged_baseline_keys(lock_only) == \
            ["unlocked-attr::a.py::X.m.a"]

    def test_waiver_applies_to_own_and_next_line(self):
        f1 = analysis.Finding("unlocked-attr", "x.py", "s", "m", line=3)
        f2 = analysis.Finding("unlocked-attr", "x.py", "s2", "m", line=9)
        src = "a\nb\n# lint: waive(unlocked-attr): reason\nc\n"
        w = analysis.waivers_in(src)
        assert w == {3: {"unlocked-attr"}}
        # covered: finding ON the waiver line or the line after
        assert "unlocked-attr" in w.get(f1.line, set()) | w.get(
            f1.line - 1, set())
        assert not (w.get(f2.line, set()) | w.get(f2.line - 1, set()))


class TestKernelRegistry:
    def test_pallas_call_drift_guard(self):
        """The test_quality.py POLICIES-sweep pattern for kernels: the
        source grep for literal ``pl.pallas_call(`` sites must equal the
        registry's per-file counts — an unregistered new kernel (or a
        registry entry for a removed one) fails the suite."""
        grepped = kernel_audit.pallas_call_sites(ROOT)
        registered = kernel_audit.registered_counts()
        assert grepped == registered, (
            f"pallas_call sites drifted from the analysis registry.\n"
            f"unregistered: "
            f"{ {k: v for k, v in grepped.items() if registered.get(k) != v} }\n"
            f"stale registry: "
            f"{ {k: v for k, v in registered.items() if grepped.get(k) != v} }\n"
            "— register the site (with at least one traced variant) in "
            "raft_tpu/analysis/kernel_audit.SITES")

    def test_every_site_traced_and_audited(self, tree_run):
        """Every registered site must produce at least one audited
        pallas_call report, and the audited variant surface must cover
        the ISSUE 14 floor (~14 registered+audited configurations)."""
        findings, reports = tree_run
        audited_sites = {r.site for r in reports}
        registered = {s.name for s in kernel_audit.SITES}
        assert audited_sites == registered, (
            f"sites without an audited trace: "
            f"{registered - audited_sites}")
        assert len(reports) >= 14, (
            f"only {len(reports)} audited kernel configurations — the "
            "registry lost variant coverage")
        # no variant silently failed to trace (a trace failure IS a
        # finding, so it is caught by the baseline gate too — this
        # asserts the stronger property that none is even baselined)
        assert not [f for f in findings if f.rule == "trace-failed"]

    def test_vmem_reports_are_sane(self, tree_run):
        """Footprints must be positive and inside the budget for every
        current variant (the budget rule fires above it)."""
        _, reports = tree_run
        budget = int(min(kernel_audit.VMEM_BUDGETS_BYTES.values())
                     * kernel_audit.VMEM_OCCUPANCY)
        for r in reports:
            assert r.vmem_total_bytes > 0, r.site
            assert r.vmem_total_bytes <= budget, (r.site, r.variant)
            assert r.dma_waits >= r.dma_starts, (r.site, r.variant)


def _toy_kernel_eqn(scratch_mib: int = 0, unwaited_dma: bool = False,
                    unpaired_sem: bool = False, misaligned: bool = False,
                    use_repeat: bool = False):
    """Trace a tiny fixture kernel with the requested violation injected
    and return its pallas_call equation (shape-trace only, never run)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, o_ref, *scratch):
        refs = list(scratch)
        if unwaited_dma or unpaired_sem:
            sem = refs.pop()
        if scratch_mib or misaligned:
            scr = refs.pop(0)
            scr[:] = jnp.zeros_like(scr)
        if unwaited_dma:
            c = pltpu.make_async_copy(x_ref, o_ref, sem)
            c.start()            # deliberately never waited
        if unpaired_sem:
            pltpu.semaphore_signal(sem, 1)   # deliberately never waited
        x = x_ref[...]
        if use_repeat:
            r = pltpu.repeat(x.astype(jnp.int32), 2, axis=1)
            o_ref[...] = x + r[:, :x.shape[1]].astype(jnp.float32)
        else:
            o_ref[...] = x * 2.0

    scratch_shapes = []
    if scratch_mib:
        rows = (scratch_mib << 20) // (128 * 4)
        scratch_shapes.append(pltpu.VMEM((rows, 128), jnp.float32))
    if misaligned:
        scratch_shapes.append(pltpu.VMEM((3, 96), jnp.float32))
    if unwaited_dma:
        scratch_shapes.append(pltpu.SemaphoreType.DMA)
    elif unpaired_sem:
        scratch_shapes.append(pltpu.SemaphoreType.REGULAR)

    def f(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=scratch_shapes,
            interpret=True,
        )(x)

    closed = jax.make_jaxpr(f)(jnp.zeros((8, 128), jnp.float32))
    (eqn,) = kernel_audit.pallas_eqns(closed)
    return eqn


class TestInjectedKernelViolations:
    """Each kernel rule class must actually catch its bug when one is
    deliberately injected (the ISSUE 14 acceptance fixtures)."""

    def _rules(self, eqn):
        _rep, issues = kernel_audit.audit_eqn("toy", "v", eqn)
        return {r for r, _m in issues}

    def test_clean_kernel_has_no_findings(self):
        assert self._rules(_toy_kernel_eqn()) == set()

    def test_vmem_overflow_caught(self):
        assert "vmem-budget" in self._rules(_toy_kernel_eqn(scratch_mib=13))

    def test_unwaited_dma_caught(self):
        assert "dma-unwaited" in self._rules(
            _toy_kernel_eqn(unwaited_dma=True))

    def test_unpaired_regular_semaphore_caught(self):
        assert "sem-unpaired" in self._rules(
            _toy_kernel_eqn(unpaired_sem=True))

    def test_misalignment_caught(self):
        rules = self._rules(_toy_kernel_eqn(misaligned=True))
        assert "lane-misaligned" in rules
        assert "sublane-misaligned" in rules

    def test_fragile_repeat_caught(self):
        assert "fragile-repeat" in self._rules(
            _toy_kernel_eqn(use_repeat=True))


class TestInjectedHotpathViolations:
    def test_unconditional_sync_caught_and_probe_exempt(self):
        src = (
            "import jax\n"
            "class S:\n"
            "    def _demux(self, out, probe):\n"
            "        jax.block_until_ready(out)\n"       # unconditional
            "        if probe:\n"
            "            jax.block_until_ready(out)\n"   # sampled: fine
            "    def warmup_all(self, out):\n"
            "        jax.block_until_ready(out)\n"       # off-path: fine
        )
        fs = hotpath_audit.sync_lint_source(src, "fixture.py")
        assert len(fs) == 1
        assert fs[0].rule == "hotpath-sync" and fs[0].line == 4

    def test_sync_inside_if_condition_caught(self):
        """The condition expression runs unconditionally — a sync there
        must not inherit its own `if` as probe cover."""
        src = ("import jax\n"
               "def serve(flag):\n"
               "    if jax.device_get(flag):\n"
               "        pass\n")
        fs = hotpath_audit.sync_lint_source(src, "fixture.py")
        assert [f.rule for f in fs] == ["hotpath-sync"]

    def test_callback_in_searcher_closure_caught(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        def bad_searcher(q):
            return jax.pure_callback(
                lambda x: np.asarray(x), jax.ShapeDtypeStruct(
                    q.shape, q.dtype), q)

        stats, fs = hotpath_audit.audit_searcher(
            "bad", bad_searcher, jnp.zeros((4, 8)))
        assert [f.rule for f in fs] == ["hotpath-callback"]
        # and a clean closure audits clean + one-dispatch
        stats, fs = hotpath_audit.audit_searcher(
            "good", lambda q: q * 2.0, jnp.zeros((4, 8)))
        assert not fs and stats["one_dispatch"]

    def test_jit_static_hazards_caught(self):
        src = (
            "import functools, jax\n"
            "@functools.partial(jax.jit,\n"
            "                   static_argnames=('k', 'rate', 'typo'))\n"
            "def f(x, k: int, rate: float = 0.5):\n"
            "    return x\n"
        )
        fs = hotpath_audit.jit_static_lint_source(src, "fixture.py")
        rules = {f.symbol: f.rule for f in fs}
        assert rules == {"f:rate": "jit-static-float",
                         "f:typo": "jit-static-missing"}

    def test_bare_partial_jit_form_also_linted(self):
        """cagra.py spells it `@partial(jax.jit, ...)` — the bare
        imported-name form must not be a blind spot."""
        src = (
            "from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit, static_argnames=('typo',))\n"
            "def g(x):\n"
            "    return x\n"
        )
        fs = hotpath_audit.jit_static_lint_source(src, "fixture.py")
        assert [f.rule for f in fs] == ["jit-static-missing"]

    def test_sync_in_nested_def_not_covered_by_outer_probe_if(self):
        """A closure defined under `if probe:` runs later,
        unconditionally — the outer condition is not probe cover."""
        src = (
            "import jax\n"
            "def serve(out, probe):\n"
            "    if probe:\n"
            "        def cb():\n"
            "            jax.block_until_ready(out)\n"
            "        return cb\n"
        )
        fs = hotpath_audit.sync_lint_source(src, "fixture.py")
        assert [f.rule for f in fs] == ["hotpath-sync"]


_LOCK_FIXTURE = """
import threading

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
        self._state = {}
        self._count = 0
        self._free = 0

    def update(self, k, v):
        with self._lock:
            self._state[k] = v
            self._count += 1

    def racy_read(self):
        return self._count            # VIOLATION: guarded, no hold

    def racy_write(self):
        self._state["x"] = 1          # VIOLATION: guarded, no hold

    def snapshot_locked(self):
        return dict(self._state)      # caller-holds-lock convention

    def waived_read(self):
        # lint: waive(unlocked-attr): fixture-documented atomic peek
        return self._count

    def free_read(self):
        return self._free             # never written under lock: clean
"""


class TestInjectedLockViolations:
    def test_unlocked_guarded_attr_caught(self):
        fs = lock_lint.lint_source(_LOCK_FIXTURE, "fixture.py")
        got = {f.symbol for f in fs}
        assert "Engine.racy_read._count" in got
        assert "Engine.racy_write._state" in got
        assert all(f.line > 0 for f in fs)
        # the *_locked convention and the never-guarded attr stay clean
        assert not [f for f in fs if "snapshot_locked" in f.symbol]
        assert not [f for f in fs if "_free" in f.symbol]
        # the waiver is honoured inside lint_source (access-level,
        # BEFORE dedupe)
        assert not [f for f in fs if "waived_read" in f.symbol]
        assert len(fs) == 2

    def test_waived_access_does_not_shadow_later_unwaived(self):
        """A waived first peek must not dedupe away a later UNWAIVED
        access to the same attribute in the same method."""
        src = (
            "import threading\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._q = []\n"
            "    def put(self, v):\n"
            "        with self._lock:\n"
            "            self._q.append(v)\n"
            "    def peek_then_race(self):\n"
            "        # lint: waive(unlocked-attr): cheap saturation peek\n"
            "        n = len(self._q)\n"
            "        return n, list(self._q)\n"     # racy, NOT waived
        )
        fs = lock_lint.lint_source(src, "fixture.py")
        assert [f.symbol for f in fs] == ["E.peek_then_race._q"]
        assert fs[0].line == 12

    def test_nested_def_in_locked_method_still_flagged(self):
        """A `*_locked` method's DIRECT body holds the lock; a closure it
        defines runs later, off the lock — that access must still
        fire."""
        src = (
            "import threading\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def read_locked(self):\n"
            "        direct = self._n\n"          # caller holds: fine
            "        def later():\n"
            "            return self._n\n"        # runs off-lock: flag
            "        return later\n"
        )
        fs = lock_lint.lint_source(src, "fixture.py")
        assert [f.symbol for f in fs] == ["E.read_locked.later._n"]

    def test_module_global_discipline(self):
        src = (
            "import threading\n"
            "_lock = threading.Lock()\n"
            "_REG = {}\n"
            "def put(k, v):\n"
            "    with _lock:\n"
            "        _REG[k] = v\n"
            "def racy():\n"
            "    return list(_REG)\n"      # VIOLATION
            "def local_ok():\n"
            "    out = {}\n"               # locals never flagged
            "    out['a'] = 1\n"
            "    return out\n"
        )
        fs = lock_lint.lint_source(src, "fixture.py")
        assert [f.symbol for f in fs] == ["module.racy._REG"]


class TestServeTreeVerdicts:
    """The satellite fixes hold: the serving tree itself is clean —
    every surviving kernel finding is a pre-hardware warning, not a
    lock/hot-path violation."""

    def test_serve_and_mutable_lock_clean(self, tree_run):
        findings, _ = tree_run
        assert not [f for f in findings if f.rule == "unlocked-attr"], (
            [f.render() for f in findings if f.rule == "unlocked-attr"])

    def test_hotpath_clean(self, tree_run):
        findings, _ = tree_run
        hot = [f for f in findings
               if f.rule in ("hotpath-sync", "hotpath-shardmap-rebuild",
                             "jit-static-float", "jit-static-missing")]
        assert not hot, [f.render() for f in hot]

    def test_tenancy_modules_in_scan_lists(self):
        """The multi-tenant fabric (ISSUE 15) stays under the gate:
        serve/tenancy.py (weighted drain + swap flip — exactly the
        lock-discipline bug class) and serve/qcache.py must resolve
        into BOTH scan lists; a future restructure that moves them out
        of serve/ must update LOCK_MODULES/HOTPATH_MODULES too."""
        import os

        import raft_tpu
        from raft_tpu.analysis import iter_module_paths
        from raft_tpu.analysis.hotpath_audit import HOTPATH_MODULES
        from raft_tpu.analysis.lock_lint import LOCK_MODULES

        root = os.path.dirname(os.path.dirname(raft_tpu.__file__))
        for entries in (LOCK_MODULES, HOTPATH_MODULES):
            rels = set(iter_module_paths(root, entries))
            for mod in ("raft_tpu/serve/tenancy.py",
                        "raft_tpu/serve/qcache.py"):
                assert mod in rels, f"{mod} fell out of the scan list"

    def test_no_fragile_repeat_in_tree(self, tree_run):
        """The ivf_pq decode's pltpu.repeat, once baselined pending a
        chip, returned recall 0.07 on a v5e and was replaced: no kernel
        in the tree calls repeat, and none is baselined."""
        findings, _ = tree_run
        assert [f for f in findings if f.rule == "fragile-repeat"] == []
        assert not [k for k in analysis.load_baseline()
                    if k.startswith("fragile-repeat::")]
