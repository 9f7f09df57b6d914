"""Static-analysis subsystem tests (``repro.analysis``).

Three contracts:

1. every corpus pattern under ``tests/analysis_corpus/`` is flagged with
   its rule code (and the suppressed/fixed variants are not);
2. the real tree comes back clean — the AST and VMEM passes in-process,
   the jaxpr pass + CLI end-to-end in a 4-fake-device subprocess;
3. the analytic wire model (``dist.collectives``) and the jaxpr-measured
   collective operands agree byte-for-byte, with the codec registry's
   ``wire_words`` as the single source of truth.
"""
import functools
import importlib.util
import json
import pathlib

from multidevice import run_with_devices

from repro.analysis import RULES, Finding, ast_lint, suppressed_codes

CORPUS = pathlib.Path(__file__).resolve().parent / "analysis_corpus"


def _codes(findings):
    return [f.code for f in findings]


@functools.lru_cache(maxsize=1)
def _traced():
    spec = importlib.util.spec_from_file_location(
        "analysis_corpus_traced", CORPUS / "traced.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Taxonomy + suppression plumbing
# ---------------------------------------------------------------------------


def test_rule_taxonomy_bands():
    bands = {c[:6] for c in RULES}
    assert bands == {"REPRO1", "REPRO2", "REPRO3"}
    assert all(len(c) == 8 and RULES[c] for c in RULES)


def test_suppression_comment_parsing():
    lines = ["x = 1",
             "y = f()  # repro: allow REPRO104 (documented)",
             "# repro: allow REPRO102, REPRO204 (both)",
             "z = g()"]
    assert suppressed_codes(lines, 2) == {"REPRO104"}
    assert suppressed_codes(lines, 4) == {"REPRO102", "REPRO204"}  # line above
    assert suppressed_codes(lines, 1) == frozenset()
    assert Finding("REPRO104", "a.py:2", "m").to_json() == {
        "code": "REPRO104", "where": "a.py:2", "message": "m"}


# ---------------------------------------------------------------------------
# AST corpus (REPRO2xx)
# ---------------------------------------------------------------------------


def test_corpus_method_dispatch_flagged():
    f = ast_lint.lint_file(CORPUS / "method_dispatch.py",
                           relpath="repro/dist/sharded_codec.py")
    assert _codes(f) == ["REPRO201", "REPRO201"]
    # out of the collective scope the same source is legal
    assert ast_lint.lint_file(CORPUS / "method_dispatch.py",
                              relpath="repro/core/helpers.py") == []


def test_corpus_bare_pallas_flagged():
    f = ast_lint.lint_file(CORPUS / "bare_pallas.py",
                           relpath="repro/adaptive/runtime.py")
    assert _codes(f) == ["REPRO202"]
    # the same launch inside kernels/ is the sanctioned home
    assert ast_lint.lint_file(CORPUS / "bare_pallas.py",
                              relpath="repro/kernels/encode.py") == []


def test_corpus_no_interpret_flagged():
    f = ast_lint.lint_file(CORPUS / "no_interpret.py",
                           relpath="repro/kernels/ops.py")
    assert _codes(f) == ["REPRO203"]
    assert "fancy_encode" in f[0].message


def test_corpus_literal_seed_flagged():
    f = ast_lint.lint_file(CORPUS / "literal_seed.py")
    assert _codes(f) == ["REPRO204", "REPRO204"]


def test_corpus_suppression_roundtrip():
    assert ast_lint.lint_file(CORPUS / "suppressed_seed.py") == []
    # stripping the allow comment must re-arm the rule
    src = (CORPUS / "suppressed_seed.py").read_text()
    armed = "\n".join(ln for ln in src.splitlines() if "repro: allow" not in ln)
    assert _codes(ast_lint.lint_source(armed, "x.py")) == ["REPRO204"]


def test_ast_pass_real_tree_clean():
    findings, stats = ast_lint.run_pass()
    assert findings == [], [str(f) for f in findings]
    assert stats["files"] >= 60


# ---------------------------------------------------------------------------
# Jaxpr corpus (REPRO1xx) — traced in-process on whatever devices exist
# ---------------------------------------------------------------------------


def test_corpus_correlated_rng_flagged():
    from repro.analysis import jaxpr_lint

    t = _traced()
    f = jaxpr_lint.lint_trace(t.correlated_rng(), "corpus", compressed=False)
    assert _codes(f) == ["REPRO102"]
    assert "axis_index" in f[0].message
    clean = jaxpr_lint.lint_trace(t.decorrelated_rng(), "corpus", compressed=False)
    assert clean == [], [str(x) for x in clean]


def test_corpus_extra_collective_flagged():
    from repro.analysis import jaxpr_lint

    t = _traced()
    closed = t.extra_collective()
    assert jaxpr_lint.count_collectives(closed) == {"all_gather": 2}
    f = jaxpr_lint.check_budget(closed, 1, "corpus")
    assert _codes(f) == ["REPRO101"]
    assert jaxpr_lint.check_budget(closed, 2, "corpus") == []


def test_corpus_f64_leak_flagged():
    from repro.analysis import jaxpr_lint

    t = _traced()
    f = jaxpr_lint.lint_trace(t.f64_leak(), "corpus", compressed=False)
    assert "REPRO103" in _codes(f)


def test_corpus_scatter_add_flagged():
    from repro.analysis import jaxpr_lint

    t = _traced()
    f = jaxpr_lint.lint_trace(t.scatter_add(), "corpus", compressed=False)
    assert _codes(f) == ["REPRO104"]


def test_corpus_suppressed_trace_site_stays_suppressed():
    """A jaxpr finding is anchored to the user source line that bound the
    equation; the allow comment there must silence it.  If anchoring breaks
    (a swallowed error in the source lookup) the finding comes back."""
    from repro.analysis import jaxpr_lint

    t = _traced()
    f = jaxpr_lint.lint_trace(t.scatter_add(), "corpus", compressed=False)
    assert f[0].where.startswith(str(CORPUS / "traced.py") + ":"), f[0].where
    assert jaxpr_lint.lint_trace(t.suppressed_scatter_add(), "corpus",
                                 compressed=False) == []


def test_corpus_wire_dtype_flagged():
    from repro.analysis import jaxpr_lint

    t = _traced()
    closed = t.wire_f32()
    f = jaxpr_lint.lint_trace(closed, "corpus", compressed=True)
    assert _codes(f) == ["REPRO105"]
    # the fp32 pmean of dsgd is that mode's contract, not a finding
    assert jaxpr_lint.lint_trace(closed, "corpus", compressed=False) == []


# ---------------------------------------------------------------------------
# VMEM corpus + the real kernel surface (REPRO3xx)
# ---------------------------------------------------------------------------


def test_corpus_vmem_blowout_flagged():
    from repro.analysis import vmem

    findings, table = vmem.estimate({"blowout": _traced().vmem_blowout_thunk()})
    assert _codes(findings) == ["REPRO301"]
    assert table[0].vmem_bytes > vmem.DEFAULT_BUDGET
    # a budget override admits the same kernel
    ok, _ = vmem.estimate({"blowout": _traced().vmem_blowout_thunk()},
                          budgets={"blowout": 1 << 30})
    assert ok == []


def test_vmem_stale_wiring_detected():
    from repro.analysis import vmem

    findings, table = vmem.estimate({"nothing": lambda: None})
    assert _codes(findings) == ["REPRO301"] and table == []
    assert "stale" in findings[0].message


def test_vmem_real_kernels_within_budget():
    from repro.analysis import vmem

    findings, table = vmem.estimate(vmem.default_thunks())
    assert findings == [], [str(f) for f in findings]
    assert len(table) >= 17
    assert all(e.vmem_bytes <= e.budget_bytes for e in table)


# ---------------------------------------------------------------------------
# Real tree end-to-end: the CLI over 4 fake devices (jaxpr pass included)
# ---------------------------------------------------------------------------


def test_cli_quick_real_tree_clean(tmp_path):
    out = tmp_path / "ANALYSIS.json"
    run_with_devices(["-m", "repro.analysis", "--quick", "--json", out], n=4)
    rep = json.loads(out.read_text())
    assert rep["version"] == 1 and rep["clean"] is True and rep["findings"] == []
    per = rep["passes"]["jaxpr"]["per_trace"]
    assert rep["passes"]["jaxpr"]["traces"] == len(per) >= 24
    # the PR 2 collective counts, pinned through the registry budgets
    assert per["sync:faithful/tqsgd"]["collectives"] == {"all_gather": 1}
    assert per["sync:two_phase/tqsgd"]["collectives"] == {
        "all_to_all": 1, "all_gather": 1}
    assert sum(per["sync:hierarchical/tqsgd"]["collectives"].values()) == 3
    assert per["sync:dsgd/tqsgd"]["collectives"] == {
        "psum": per["sync:dsgd/tqsgd"]["n_buckets"]}
    for label, row in per.items():
        if "budget" in row:
            assert sum(row["collectives"].values()) <= row["budget"], (label, row)
    assert rep["passes"]["ast"]["files"] >= 60
    assert rep["passes"]["vmem"]["kernels"] >= 17


def test_ast_pass_over_corpus_reports_findings():
    # a corpus-seeded tree must fail: point the AST pass at the corpus dir
    findings, stats = ast_lint.run_pass(CORPUS)
    assert stats["files"] >= 6
    # unscoped rules fire (bare pallas_call, literal seeds); the
    # path-scoped REPRO201/203 stay off without their relpaths
    assert sorted({f.code for f in findings}) == ["REPRO202", "REPRO204"]


# ---------------------------------------------------------------------------
# Wire cross-check: analytic bytes vs jaxpr-measured collective operands
# ---------------------------------------------------------------------------


def test_wire_model_matches_traced_collectives():
    """``encode_hbm_bytes``/``decode_hbm_bytes`` and the traced all-gather
    operand must all derive from the registry's ``wire_words``."""
    code = """
import jax
from repro.analysis import jaxpr_lint as jl
from repro.core.codecs import bucket_cfgs, get_codec
from repro.core.compressors import CompressorConfig
from repro.dist.collectives import decode_hbm_bytes, encode_hbm_bytes
from repro.dist.train_step import TrainStepConfig, local_bucket_sizes

st = jl.sync_trace("tqsgd", "faithful")
wires = [w for w in jl.collective_wire_sizes(st.closed)
         if w.primitive == "all_gather"]
assert len(wires) == 1, wires
assert all(d == "uint32" for d in wires[0].dtypes), wires[0]

# rebuild the exact bucket geometry the harness traced
cfg = CompressorConfig(method="tqsgd", bits=3, rank=2, approx_gmin=True)
mesh = jax.make_mesh((jl._N_DEV,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
params_like, pspecs = jl._param_trees()
ts = TrainStepConfig(sync="faithful", compressor=cfg, bucket_mb=jl._BUCKET_MB)
sizes = [int(s) for s in local_bucket_sizes(params_like, mesh, pspecs, ts)]
bits = jl._bits_plan("tqsgd", len(sizes))
assert len(sizes) == st.n_buckets >= 2, (sizes, st.n_buckets)

cfgs = bucket_cfgs(cfg, len(sizes), bits)
words = sum(get_codec(c.method).wire_words(c, n) for c, n in zip(cfgs, sizes))
# 1) the traced operand IS the registry wire: 4 bytes per uint32 word
assert wires[0].in_bytes == 4 * words, (wires[0].in_bytes, words)
assert wires[0].out_bytes == jl._N_DEV * wires[0].in_bytes

# 2) the decode model reads exactly peers x that wire (+ the (n,) output)
peers = jl._N_DEV
got = decode_hbm_bytes(cfg, sizes, peers, True, bits)
assert got == peers * wires[0].in_bytes + 4.0 * sum(sizes), got

# 3) the encode model's wire term is the measured wire minus the
#    codebook words the kernel writes straight from VMEM
got = encode_hbm_bytes(cfg, sizes, True, ef=False, adaptive=False, bits=bits)
codebook = sum(4.0 * (c.s + 1) for c in cfgs)
assert got == 8.0 * sum(sizes) + 4 * words - codebook, got
print("WIRE-CHECK-OK")
"""
    assert "WIRE-CHECK-OK" in run_with_devices(code, n=4)
