"""apexlint unit tests (ISSUE 12): every Tier-A rule must catch its
fixture and pass its clean twin; the linter machinery (suppressions,
baseline diff, fingerprints, env registry) is pinned; and the Tier-B
auditor unit plants a monolithic psum inside an overlap scope and
asserts the census flags it.

Fixture style: in-memory modules via ``rules.module_from_source`` —
the same ModuleInfo path the real linter walks, minus the filesystem.
The full-matrix Tier-B audit is exercised by the ``static_audit``
dryrun phase and a slow-marked test here; the default-run tests only
*trace* tiny functions (no compiles), keeping this file cheap inside
the tier-1 window.
"""

import json
import os

import pytest

from apex_tpu.analysis import env_registry, linter
from apex_tpu.analysis.rules import module_from_source, rules_by_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RULES = rules_by_id()


@pytest.fixture(scope="module")
def repo_findings():
    """ONE full-repo lint shared by every at-head assertion in this
    file (the parse+call-graph+donation pass is the expensive part)."""
    return linter.lint(REPO)


def run_rule(rule_id, source, relpath="apex_tpu/_fixture.py"):
    return list(RULES[rule_id].check(
        module_from_source(source, relpath)))


# ---------------------------------------------------------------------------
# APX2xx — env-var discipline
# ---------------------------------------------------------------------------


class TestEnvRules:
    def test_unregistered_env_read_fires(self):
        fs = run_rule(
            "APX201",
            'import os\nv = os.environ.get("APEX_TPU_NOT_A_THING")\n')
        assert len(fs) == 1 and "APEX_TPU_NOT_A_THING" in fs[0].message

    def test_registered_env_read_clean(self):
        assert not run_rule(
            "APX201",
            'import os\nv = os.environ.get("APEX_TPU_PALLAS_INTERPRET")\n')

    def test_subscript_read_fires(self):
        assert run_rule(
            "APX201", 'import os\nv = os.environ["APEX_TPU_BOGUS"]\n')

    def test_name_built_at_run_time_fires(self):
        # no family rows: an f-string's static prefix is no row's name,
        # not even where registered names start with it
        assert run_rule(
            "APX201",
            'import os\n'
            'v = os.environ.get(f"APEX_TPU_DISABLE_{name}")\n')
        assert run_rule(
            "APX201",
            'import os\n'
            'v = os.environ.get(f"APEX_TPU_BOGUS_{name}")\n')

    def test_non_apex_names_ignored(self):
        assert not run_rule(
            "APX201", 'import os\nv = os.environ.get("HOME")\n')

    def test_lookup_is_by_exact_name(self):
        row = env_registry.lookup("APEX_TPU_DISABLE_NATIVE")
        assert row is not None and row.name == "APEX_TPU_DISABLE_NATIVE"
        assert env_registry.lookup("APEX_TPU_DISABLE_FLASH_ATTENTION") is None
        assert env_registry.lookup("APEX_TPU_NOPE") is None

    def test_docs_sync_clean_at_head(self):
        fs = list(RULES["APX202"].check_repo([], REPO))
        assert not fs, "\n".join(f.message for f in fs)

    def test_docs_sync_catches_undocumented_row(self, monkeypatch):
        bogus = dict(env_registry.ENV_REGISTRY)
        bogus["APEX_TPU_PHANTOM_KNOB"] = env_registry.EnvVar(
            "APEX_TPU_PHANTOM_KNOB", "nowhere",
            "docs/static_analysis.md", "not actually documented")
        monkeypatch.setattr(env_registry, "ENV_REGISTRY", bogus)
        fs = list(RULES["APX202"].check_repo([], REPO))
        assert len(fs) == 1 and "APEX_TPU_PHANTOM_KNOB" in fs[0].message

    def test_private_global_owner_file_exempt(self):
        # metrics.py owns _REGISTRY; the same source elsewhere fires
        src = "def shutdown():\n    global _REGISTRY\n    x = _REGISTRY\n"
        assert not run_rule("APX103", src,
                            "apex_tpu/observability/metrics.py")
        assert run_rule("APX103", src, "apex_tpu/comm/reduce.py")

    def test_env_table_sync_clean_at_head(self):
        mods = linter._parse_modules(
            REPO, ("apex_tpu/observability/metrics.py",))
        fs = list(RULES["APX203"].check_repo(mods, REPO))
        assert not fs, "\n".join(f.message for f in fs)

    def test_env_table_sync_catches_drift(self):
        # a doctored metrics.py with an extra telemetry var must trip
        # the statically-parsed sync check
        fake = module_from_source(
            'ENV_PREFIX = "APEX_TPU_TELEMETRY"\n'
            'ENV_VARS = {"": 1, "_STDERR": 1, "_NEWVAR": 1}\n',
            "apex_tpu/observability/metrics.py")
        fs = list(RULES["APX203"].check_repo([fake], REPO))
        assert fs and "_NEWVAR" in fs[0].message


# ---------------------------------------------------------------------------
# APX3xx — host sync / nondeterminism under a trace
# ---------------------------------------------------------------------------

_JIT_HEADER = "import jax\nimport numpy as np\nimport time\n"


class TestHostSyncRule:
    def test_item_in_jitted_fn_fires(self):
        fs = run_rule("APX301", _JIT_HEADER +
                      "@jax.jit\ndef f(x):\n    return x.item()\n")
        assert len(fs) == 1 and ".item()" in fs[0].message

    def test_item_in_host_fn_clean(self):
        assert not run_rule(
            "APX301", _JIT_HEADER + "def f(x):\n    return x.item()\n")

    def test_float_on_param_in_while_body_fires(self):
        src = _JIT_HEADER + (
            "def loop(x):\n"
            "    def body(c):\n"
            "        return c + float(c)\n"
            "    return jax.lax.while_loop(lambda c: True, body, x)\n")
        fs = run_rule("APX301", src)
        assert fs and "float(" in fs[0].message

    def test_float_on_shape_is_static(self):
        src = _JIT_HEADER + (
            "@jax.jit\ndef f(x):\n    return x * int(x.shape[0])\n")
        assert not run_rule("APX301", src)

    def test_int_annotated_param_is_static(self):
        src = _JIT_HEADER + (
            "@jax.jit\ndef f(n: int):\n    return int(n) + 1\n")
        assert not run_rule("APX301", src)

    def test_np_asarray_on_traced_value_fires(self):
        src = _JIT_HEADER + (
            "@jax.jit\ndef f(x):\n    return np.asarray(x) + 1\n")
        assert run_rule("APX301", src)

    def test_transitive_callee_fires(self):
        # f is jitted, g is plain — but reachable from f, so g's sync
        # is inside the trace
        src = _JIT_HEADER + (
            "def g(x):\n    return x.item()\n"
            "@jax.jit\ndef f(x):\n    return g(x)\n")
        fs = run_rule("APX301", src)
        assert fs and "g" in fs[0].message

    def test_suppression_comment_respected(self):
        # suppression is applied by the linter layer, so drive lint()
        # over a temp module
        import tempfile

        src = _JIT_HEADER + (
            "@jax.jit\ndef f(x):\n"
            "    return x.item()   # apexlint: disable=APX301\n")
        with tempfile.TemporaryDirectory() as d:
            pkg = os.path.join(d, "apex_tpu")
            os.makedirs(pkg)
            with open(os.path.join(pkg, "m.py"), "w") as f:
                f.write(src)
            assert not linter.lint(d, targets=("apex_tpu",),
                                   rules=[RULES["APX301"]])
            with open(os.path.join(pkg, "m.py"), "w") as f:
                f.write(src.replace("   # apexlint: disable=APX301",
                                    ""))
            assert linter.lint(d, targets=("apex_tpu",),
                               rules=[RULES["APX301"]])


class TestNondeterminismRule:
    def test_time_in_scan_body_fires(self):
        src = _JIT_HEADER + (
            "def step(c, x):\n    return c, time.time()\n"
            "def run(xs):\n    return jax.lax.scan(step, 0, xs)\n")
        fs = run_rule("APX302", src)
        assert fs and "host clock" in fs[0].message

    def test_np_random_in_jit_fires(self):
        src = _JIT_HEADER + (
            "@jax.jit\ndef f(x):\n    return x + np.random.randn()\n")
        fs = run_rule("APX302", src)
        assert fs and "numpy RNG" in fs[0].message

    def test_jax_random_is_clean(self):
        src = _JIT_HEADER + (
            "@jax.jit\ndef f(key, x):\n"
            "    return x + jax.random.normal(key, x.shape)\n")
        assert not run_rule("APX302", src)

    def test_time_on_host_clean(self):
        assert not run_rule(
            "APX302",
            _JIT_HEADER + "def poll():\n    return time.time()\n")


class TestReviewRegressions:
    """Pins for the review-pass fixes: each of these was an executed
    counterexample before the fix."""

    def test_suppression_comma_space_list(self, tmp_path):
        # '# apexlint: disable=APX301, APX302' (space after comma)
        # must suppress BOTH ids
        pkg = tmp_path / "apex_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            _JIT_HEADER +
            "@jax.jit\ndef f(x):\n"
            "    return x.item() + time.time()"
            "   # apexlint: disable=APX301, APX302\n")
        fs = linter.lint(str(tmp_path), targets=("apex_tpu",),
                         rules=[RULES["APX301"], RULES["APX302"]])
        assert not fs, [f.message for f in fs]

    def test_fstring_metric_violation_reports_once(self):
        fs = run_rule(
            "APX105", 'reg.counter(f"moe.{name}_bytes").inc(1)\n')
        assert len(fs) == 1

    def test_math_exemption_is_subtree_scoped(self):
        # the math call's own subtree is exempt; a traced param
        # ELSEWHERE in the expression still flags, in either operand
        # order
        for expr in ("float(x * math.sqrt(2.0))",
                     "float(math.sqrt(2.0) * x)"):
            src = ("import jax, math\n"
                   f"@jax.jit\ndef f(x):\n    return {expr}\n")
            assert run_rule("APX301", src), expr
        assert not run_rule(
            "APX301",
            "import jax, math\n"
            "@jax.jit\ndef f(x):\n"
            "    return x * math.prod(x.shape)\n")

    def test_kind_tallies_shared_by_gate_and_emission(self):
        from apex_tpu.analysis.jaxpr_audit import kind_tallies

        t = kind_tallies(
            {"psum": 2, "reduce_scatter": 1},
            {"collectives.psum.calls": 1.0,
             "collectives.pmean.calls": 1.0,
             "collectives.psum_scatter.calls": 1.0},
            ("psum", "psum_scatter"))
        assert t["psum"] == (2, 2.0)          # pmean folds into psum
        assert t["psum_scatter"] == (1, 1.0)  # reduce_scatter prim


# ---------------------------------------------------------------------------
# APX401 — donation safety
# ---------------------------------------------------------------------------


def run_donation(source, relpath="apex_tpu/_fixture.py"):
    mod = module_from_source(source, relpath)
    return list(RULES["APX401"].check_repo([mod], REPO))


class TestDonationRule:
    def test_use_after_donation_fires(self):
        src = (
            "import jax\n"
            "def make(f, state, x):\n"
            "    step = jax.jit(f, donate_argnums=(0,))\n"
            "    new = step(state, x)\n"
            "    return new, state.sum()\n")
        fs = run_donation(src)
        assert len(fs) == 1 and "'state'" in fs[0].message

    def test_rebinding_through_the_call_is_clean(self):
        src = (
            "import jax\n"
            "def make(f, state, xs):\n"
            "    step = jax.jit(f, donate_argnums=(0,))\n"
            "    for x in xs:\n"
            "        state = step(state, x)\n"
            "    return state\n")
        assert not run_donation(src)

    def test_prefix_rebind_kills_the_path(self):
        # self.cache = {...} rebinds self.cache["k"] — the engine's
        # real idiom (a regression here re-flags serving/engine.py)
        src = (
            "import jax, functools\n"
            "@functools.partial(jax.jit, donate_argnames=('pool',))\n"
            "def insert(pool, ks):\n"
            "    return pool\n"
            "class E:\n"
            "    def write(self, ks):\n"
            "        k = insert(self.cache['k'], ks)\n"
            "        self.cache = {'k': k}\n"
            "        return self.cache['k'].shape\n")
        assert not run_donation(src)

    def test_donate_argnames_decorator_maps_positions(self):
        src = (
            "import jax, functools\n"
            "@functools.partial(jax.jit, donate_argnames=('pool',))\n"
            "def insert(pool, ks):\n"
            "    return pool\n"
            "def caller(pool, ks):\n"
            "    out = insert(pool, ks)\n"
            "    return out, pool.shape\n")
        fs = run_donation(src)
        assert len(fs) == 1 and "'pool'" in fs[0].message

    def test_repo_clean_at_head(self, repo_findings):
        fs = [f for f in repo_findings if f.rule == "APX401"]
        assert not fs, "\n".join(f"{f.path}:{f.line} {f.message}"
                                 for f in fs)


# ---------------------------------------------------------------------------
# linter machinery: baseline diff, fingerprints, skip-file, --changed
# ---------------------------------------------------------------------------


class TestLinterMachinery:
    def _temp_repo(self, d, body):
        pkg = os.path.join(d, "apex_tpu")
        os.makedirs(pkg, exist_ok=True)
        with open(os.path.join(pkg, "m.py"), "w") as f:
            f.write(body)
        return d

    def test_fingerprints_are_line_number_free(self, tmp_path):
        body = "r = MetricsRegistry(s)\n"
        d = self._temp_repo(str(tmp_path), body)
        fs1 = linter.lint(d, targets=("apex_tpu",),
                          rules=[RULES["APX102"]])
        (fp1, _), = linter.fingerprints(fs1)
        # shift the finding down two lines: fingerprint must not move
        self._temp_repo(d, "import x\nimport y\n" + body)
        fs2 = linter.lint(d, targets=("apex_tpu",),
                          rules=[RULES["APX102"]])
        (fp2, f2), = linter.fingerprints(fs2)
        assert fp1 == fp2 and f2.line == 3

    def test_identical_snippets_get_ordinals(self, tmp_path):
        body = "r = MetricsRegistry(s)\nr = MetricsRegistry(s)\n"
        d = self._temp_repo(str(tmp_path), body)
        fs = linter.lint(d, targets=("apex_tpu",),
                         rules=[RULES["APX102"]])
        fps = [fp for fp, _ in linter.fingerprints(fs)]
        assert len(fps) == 2 and len(set(fps)) == 2
        assert fps[0].endswith(":0") and fps[1].endswith(":1")

    def test_baseline_roundtrip_and_diff(self, tmp_path):
        d = self._temp_repo(str(tmp_path),
                            "r = MetricsRegistry(s)\n")
        fs = linter.lint(d, targets=("apex_tpu",),
                         rules=[RULES["APX102"]])
        linter.write_baseline(d, fs)
        new, stale = linter.diff_baseline(d, fs)
        assert not new and not stale
        with open(os.path.join(d, linter.BASELINE_FILE)) as f:
            doc = json.load(f)
        assert doc["entries"][0]["justification"].startswith(
            "FILL-ME-IN")
        # fix the finding: the entry goes stale
        new, stale = linter.diff_baseline(d, [])
        assert not new and len(stale) == 1
        # a different finding is NEW even with a baseline present
        self._temp_repo(d, "r2 = MetricsRegistry(t)\n")
        fs2 = linter.lint(d, targets=("apex_tpu",),
                          rules=[RULES["APX102"]])
        new, _ = linter.diff_baseline(d, fs2)
        assert len(new) == 1

    def test_skip_file_header(self, tmp_path):
        d = self._temp_repo(
            str(tmp_path),
            "# apexlint: skip-file\nr = MetricsRegistry(s)\n")
        assert not linter.lint(d, targets=("apex_tpu",),
                               rules=[RULES["APX102"]])

    def test_repo_lint_is_clean_or_baselined(self, repo_findings):
        """THE enforcement pin: the real repo must stay clean against
        its committed baseline (currently empty — keep it so)."""
        new, stale = linter.diff_baseline(REPO, repo_findings)
        assert not new, "new apexlint findings:\n" + "\n".join(
            f"  {fp} {f.path}:{f.line} {f.message}" for fp, f in new)
        assert not stale, (
            "stale baseline entries (delete them):\n" + "\n".join(
                e["fingerprint"] for e in stale))


# ---------------------------------------------------------------------------
# Tier B — jaxpr auditor units
# ---------------------------------------------------------------------------


class TestJaxprAudit:
    def test_planted_psum_in_overlap_scope_is_flagged(self):
        """THE acceptance unit: a monolithic psum planted inside an
        overlap scope must show up in the census and fail the
        ring-only check."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.analysis import jaxpr_audit

        n = min(8, len(jax.devices()))
        mesh = Mesh(np.array(jax.devices()[:n]), ("tp",))
        planted = jax.shard_map(
            lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
            in_specs=P("tp"), out_specs=P())
        rep = jaxpr_audit.audit_overlap_trace(
            planted, jnp.ones((n, 4)))
        assert not rep.ok
        assert rep.census.get("psum") == 1
        assert any("monolithic psum" in f for f in rep.findings)

    def test_ring_trace_is_clean_and_counted(self):
        """The real ring decomposition under the same helper: ppermute
        only, and the census agrees with collectives.ppermute.calls."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.analysis import jaxpr_audit
        from apex_tpu.ops.collective_matmul import ring_all_gather

        n = min(8, len(jax.devices()))
        mesh = Mesh(np.array(jax.devices()[:n]), ("tp",))
        ring = jax.shard_map(
            lambda x: ring_all_gather(x, "tp"), mesh=mesh,
            in_specs=P("tp"), out_specs=P("tp"))
        rep = jaxpr_audit.audit_overlap_trace(ring, jnp.ones((n, 4)))
        assert rep.ok, rep.findings
        assert rep.census.get("ppermute", 0) == n - 1
        assert rep.counted.get("collectives.ppermute.calls") == n - 1
        assert rep.counted.get("collectives.ring.hops") == n - 1

    def test_census_vs_counters_drift_detector(self):
        from apex_tpu.analysis.jaxpr_audit import \
            check_census_vs_counters

        # census > counters: always a finding (uncounted collective)
        fs = check_census_vs_counters(
            {"all_gather": 3}, {"collectives.all_gather.calls": 2.0},
            ("all_gather",))
        assert fs and "drift" in fs[0]
        # counters > census: only under exact policy
        assert not check_census_vs_counters(
            {"all_gather": 1}, {"collectives.all_gather.calls": 2.0},
            ("all_gather",))
        assert check_census_vs_counters(
            {"all_gather": 1}, {"collectives.all_gather.calls": 2.0},
            ("all_gather",), policy="exact")
        # agreement is quiet
        assert not check_census_vs_counters(
            {"all_gather": 2}, {"collectives.all_gather.calls": 2.0},
            ("all_gather",), policy="exact")

    def test_dead_expensive_eqn_flagged_cheap_noted(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.analysis.jaxpr_audit import check_dead_eqns

        def f(x, w):
            dead = x @ w          # dropped matmul: real lost compute
            cheap = x + 1.0       # dropped elementwise: trace noise
            return x.sum()

        jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)), jnp.ones((4, 4)))
        findings, notes = check_dead_eqns(jaxpr)
        assert len(findings) == 1 and "dot_general" in findings[0]
        assert notes and "cheap dead" in notes[0]

    def test_upcast_detector_and_allowlist(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.analysis.jaxpr_audit import check_upcasts

        def suspicious_mixer(x):
            h = x.astype(jnp.bfloat16)
            return (h.astype(jnp.float32) * 2.0).sum()

        jaxpr = jax.make_jaxpr(suspicious_mixer)(jnp.ones((8,)))
        findings, _ = check_upcasts(jaxpr)
        assert findings and "suspicious_mixer" in findings[0]
        # the same convert under an allowlisted name passes
        findings, _ = check_upcasts(
            jaxpr, allowlist=("suspicious_mixer",))
        assert not findings

    def test_donation_check_detects_lowered_alias(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.analysis.jaxpr_audit import check_donation

        def step(s, x):
            return s + x

        donated = jax.jit(step, donate_argnums=0)
        plain = jax.jit(step)
        args = (jnp.ones((4,)), jnp.ones((4,)))
        assert not check_donation(donated, args)
        assert check_donation(plain, args)

    @pytest.mark.slow
    def test_full_entry_matrix_is_green(self):
        """The whole Tier-B matrix (also gated by the static_audit
        dryrun phase; slow-marked here to stay out of the tier-1
        window — tracing only, ~15 s)."""
        from apex_tpu.analysis import jaxpr_audit

        reports = jaxpr_audit.run_audit()
        bad = {r.name: r.findings for r in reports if not r.ok}
        assert not bad, bad
        names = {r.name for r in reports}
        assert names == set(jaxpr_audit.ENTRY_POINTS)
