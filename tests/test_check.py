"""repro.check analyzer-suite tests (DESIGN.md §13):

- grid-race classification of the production kernels and the known-racy
  fixture; the per-backend legality verdict `select_impl` derives from it;
- boundary lint: engine modules clean, the leaky fixture flagged on the
  right rules, seeded f64/.item() injections into real engine source
  caught, the planner fixture's PLN hits;
- dtype-flow: synthetic bf16 dot/arithmetic flagged, storage-only clean;
- waiver mechanics and the in-process CLI (exit codes, --list-rules,
  --format=json).
"""
import json
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.check import config
from repro.check.boundary import check_file, check_source
from repro.check.findings import RULES, Finding, apply_waivers
from repro.check.pallas_race import all_reports, analyze_callable, get_report
from repro.kernels.dispatch import resolve_interpret, select_impl

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "src/repro/check/fixtures"


# ---------------------------------------------------------------------------
# grid-race detector
# ---------------------------------------------------------------------------
EXPECTED_CLASSIFICATION = {
    "weighted_agg.weighted_agg_2d": ("parallel-safe", ()),
    "weighted_agg.ring_agg_2d": ("sequential-axis-required", (1,)),
    "cross_entropy.cross_entropy_tiled": ("sequential-axis-required", (1,)),
    "decode_attention.decode_attention_bkv": (
        "sequential-axis-required", (1,)),
    "swa_attention.swa_attention_bhsd": ("sequential-axis-required", (2,)),
}


def test_production_kernels_classify_as_documented():
    for rep in all_reports():
        cls, axes = EXPECTED_CLASSIFICATION[rep.kernel_id]
        assert rep.classification == cls, rep
        assert rep.revisit_axes == axes, rep


def test_legality_verdict_follows_classification():
    safe = get_report("weighted_agg.weighted_agg_2d")
    seq = get_report("weighted_agg.ring_agg_2d")
    # interpreter-only on cpu (no Mosaic lowering), gpu needs parallel-safe,
    # tpu sequentialises the revisited axis
    assert safe.compiled_legal == {"cpu": False, "gpu": True, "tpu": True}
    assert seq.compiled_legal == {"cpu": False, "gpu": False, "tpu": True}


def test_racy_fixture_classifies_racy_and_illegal_everywhere():
    from repro.check.fixtures.racy_kernel import invoke

    rep = analyze_callable("fixtures.racy_sum", "racy_sum", invoke)
    assert rep.classification == "racy"
    assert rep.compiled_legal == {"cpu": False, "gpu": False, "tpu": False}


def test_select_impl_truth_table():
    seq = get_report("weighted_agg.ring_agg_2d")
    safe = get_report("weighted_agg.weighted_agg_2d")
    # explicit interpret bool always wins
    assert select_impl(seq, "tpu", interpret=True) == "interpret"
    assert select_impl(seq, "cpu", interpret=False) == "compiled"
    # None resolves from the verdict
    assert select_impl(seq, "tpu") == "compiled"
    assert select_impl(seq, "gpu") == "interpret"
    assert select_impl(seq, "gpu", fallback="ref") == "fallback"
    assert select_impl(seq, "gpu", fallback="ref",
                       force_kernel=True) == "interpret"
    assert select_impl(safe, "gpu", fallback="ref") == "compiled"
    assert select_impl(safe, "cpu", fallback="ref") == "fallback"


def test_resolve_interpret_matches_backend_verdict():
    # on this host (cpu) compiled pallas is illegal -> interpreter
    assert jax.default_backend() == "cpu"
    assert resolve_interpret("weighted_agg.ring_agg_2d") is True
    assert resolve_interpret("weighted_agg.ring_agg_2d", False) is False
    assert resolve_interpret("weighted_agg.ring_agg_2d", True) is True


# ---------------------------------------------------------------------------
# boundary lint
# ---------------------------------------------------------------------------
def test_engine_modules_lint_clean():
    for suffix in config.ENGINE_MODULES:
        path = REPO / "src" / suffix
        live = [f for f in check_file(path) if not f.waived]
        assert not live, [f.format() for f in live]


def test_leaky_fixture_hits_every_bnd_rule():
    findings = check_file(FIXTURES / "leaky_engine.py")
    rules = {f.rule for f in findings}
    assert {"BND001", "BND002", "BND003", "BND004", "BND005"} <= rules, \
        [f.format() for f in findings]
    # the Python-branch and for-loop hits land on distinct lines
    bnd2_lines = {f.line for f in findings if f.rule == "BND002"}
    assert len(bnd2_lines) >= 2


def test_bad_planner_fixture_hits_pln_rules():
    src = (FIXTURES / "bad_planner.py").read_text()
    # feed it through under a planner path so the planner dual applies
    findings = check_source("src/repro/corridor/plan.py", src)
    rules = {f.rule for f in findings}
    assert "PLN001" in rules and "PLN002" in rules, \
        [f.format() for f in findings]


def test_seeded_injection_into_real_engine_is_caught():
    src = (REPO / "src/repro/core/jit_engine.py").read_text()
    anchor = "i = jnp.argmin(qt)                          # pop"
    assert anchor in src
    line = next(l for l in src.splitlines() if anchor in l)
    indent = line[:len(line) - len(line.lstrip())]
    inject = (anchor
              + f"\n{indent}bad64 = qt.astype(jnp.float64)"
              + f"\n{indent}badhost = qt[0].item()")
    findings = check_source("src/repro/core/jit_engine.py",
                            src.replace(anchor, inject, 1))
    rules = {f.rule for f in findings if not f.waived}
    assert "BND004" in rules and "BND003" in rules, \
        [f.format() for f in findings]


def test_static_argnames_are_not_tainted():
    src = textwrap.dedent("""
        import functools, jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("block",))
        def f(x, block):
            if block > 4:
                x = x * 2
            return x

        g = jax.checkpoint(f, static_argnums=(1,))
    """)
    assert check_source("t.py", src) == []


# ---------------------------------------------------------------------------
# dtype flow
# ---------------------------------------------------------------------------
def test_dtype_flow_flags_bf16_compute():
    from repro.check.dtype_flow import check_jaxpr

    def bad(a, b):
        return (a @ b).astype(jnp.float32), a + a

    x = jnp.ones((4, 4), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(bad)(x, x)
    rules = {f.rule for f in check_jaxpr(jaxpr, allow_bf16=True, path="<t>")}
    assert rules == {"DTF001", "DTF002"}
    rules = {f.rule for f in check_jaxpr(jaxpr, allow_bf16=False, path="<t>")}
    assert rules == {"DTF003"}


def test_dtype_flow_allows_bf16_storage_roles():
    from repro.check.dtype_flow import check_jaxpr

    def ok(a, b):
        wide = a.astype(jnp.float32) @ b.astype(jnp.float32)
        return wide.astype(jnp.bfloat16).reshape(-1)

    x = jnp.ones((4, 4), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(ok)(x, x)
    assert check_jaxpr(jaxpr, allow_bf16=True, path="<t>") == []


def test_engine_dtype_probes_clean():
    from repro.check.dtype_flow import probe_dtype_flow

    assert [f.format() for f in probe_dtype_flow()] == []


def test_plan_shape_probe_clean():
    from repro.check.plan_shapes import probe_plan_shapes

    assert [f.format() for f in probe_plan_shapes()] == []


# ---------------------------------------------------------------------------
# waivers + CLI
# ---------------------------------------------------------------------------
def test_waiver_suppresses_matching_rule_only():
    src = ("x = 1\n"
           "y = 2  # repro-check: waive[BND004] fixture data is f64\n"
           "z = 3\n")
    fs = [Finding("BND004", "w.py", 2, "m"),
          Finding("BND003", "w.py", 2, "m"),
          Finding("BND004", "w.py", 3, "m")]   # line below comment: waived
    out = apply_waivers(fs, {"w.py": src})
    assert [f.waived for f in out] == [True, False, True]
    assert out[0].waive_reason == "fixture data is f64"


def test_waiver_without_reason_is_ignored():
    from repro.check.findings import load_waivers

    assert load_waivers("x  # repro-check: waive[BND004]\n") == {}


def test_cli_list_rules_and_json(capsys):
    from repro.check.runner import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert all(rid in out for rid in RULES)

    assert main(["src/repro/check/findings.py", "--no-probes",
                 "--format=json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert {r["kernel_id"] for r in payload["kernels"]} == set(
        EXPECTED_CLASSIFICATION)
    assert payload["findings"] == []


def test_cli_strict_exit_codes(tmp_path, capsys):
    from repro.check.runner import main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return float(x)
    """))
    assert main([str(bad), "--no-probes", "--strict"]) == 1
    assert "BND003" in capsys.readouterr().out
    bad.write_text(bad.read_text().replace(
        "return float(x)",
        "return float(x)  # repro-check: waive[BND003] test waiver"))
    assert main([str(bad), "--no-probes", "--strict"]) == 0


def test_fixture_corpus_is_excluded_from_default_scans():
    from repro.check.runner import collect_files

    files = collect_files(["src"])
    assert files, "scan set must not be empty"
    assert not any("check/fixtures" in f.as_posix() for f in files)
