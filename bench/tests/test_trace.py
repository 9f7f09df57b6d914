"""The trace reduction, checked on a small trace recorded on a TPU v5e.

``data/tiny_trace.*`` come from ``record_trace.py``: the tiny
Qwen2-VL-shaped configuration on one chip under ``faithful`` sync (the
statistics, encode and decode kernels run, nothing is exchanged), a few
steps with the profiler on, and the compiled step's HLO text.
"""
import gzip
import json
import re
from pathlib import Path

import pytest

from bench import trace
from bench.harness import load_metric

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace")
    xplane = d / "t.xplane.pb"
    xplane.write_bytes(gzip.decompress((DATA / "tiny_trace.xplane.pb.gz").read_bytes()))
    hlo_text = gzip.decompress((DATA / "tiny_trace.hlo.txt.gz").read_bytes()).decode()
    result = json.loads((DATA / "tiny_trace.result.json").read_text())
    pd = ProfileData.from_file(str(xplane))
    hlo = trace.parse_hlo(hlo_text)
    events = trace.device_events(None, hlo, pd)
    steps = result["attempted"] - 3
    ctx = trace.summarize(events, steps=steps, flops_per_step=1e9, peaks=PEAKS,
                          workload={"bits": 3}, config={}, host=trace.host_spans(pd))
    return {"pd": pd, "hlo": hlo, "events": events, "ctx": ctx, "result": result,
            "hlo_text": hlo_text}


def test_every_device_op_is_found_in_the_step_or_the_batch(recorded):
    events = recorded["events"]
    assert events
    found = sum(e.op is not None for e in events)
    # the rest are the batch maker's ops (another program)
    assert found >= 0.9 * len(events)


def test_pallas_kernels_are_named_and_sized(recorded):
    kernels = {e.op.kernel for e in recorded["events"] if e.op is not None and e.op.kernel}
    assert "ef_correct_stats" in kernels
    assert any(k.endswith("_encode_pack_residual") for k in kernels)
    for e in recorded["events"]:
        if e.op is not None and e.op.kernel:
            assert max(e.op.operand_elements) >= 128


def test_codec_ops_carry_the_obs_scopes_or_are_the_stats_kernel(recorded):
    codec = [e for e in recorded["events"] if e.category == "codec"]
    assert codec
    assert all(re.search(r"obs\.(encode|decode)", e.op.scope)
               or e.op.kernel in trace.STATS_KERNELS for e in codec)
    assert any(e.op.kernel == "ef_correct_stats" for e in codec)
    assert not [e for e in recorded["events"] if e.category == "collective"]


def test_containers_are_left_out_and_busy_fits_the_window(recorded):
    ctx = recorded["ctx"]
    assert not [e for e in recorded["events"] if e.op is not None
                and e.op.opcode in trace.CONTAINERS]
    assert 0 < ctx.busy_s <= ctx.window_s
    total = sum(e.dur_ns for e in recorded["events"]) / 1e9
    cats = sum(ctx.per_step_ms(c) for c in ("model", "codec", "sync", "collective",
                                           "optimizer")) * ctx.steps / 1e3
    assert cats == pytest.approx(total / ctx.devices)


def test_roofline_by_hand(recorded):
    ctx = recorded["ctx"]
    evs = [e for e in recorded["events"] if e.op is not None
           and e.op.kernel == "ef_correct_stats"]
    need = sum(12.0 * max(e.op.operand_elements) for e in evs)
    spent = sum(e.dur_ns for e in evs) / 1e9
    want = 100.0 * need / PEAKS["hbm_bytes_per_s"] / spent
    assert load_metric("stats_kernel_roofline_pct").read(ctx) == pytest.approx(want)
    assert 0.0 < want <= 100.0


def test_readers_on_the_recorded_trace(recorded):
    ctx = recorded["ctx"]
    idle = load_metric("device_idle_pct").read(ctx)
    assert 0.0 <= idle < 100.0
    assert load_metric("collective_ms").read(ctx) is None
    assert load_metric("codec_ms").read(ctx) > 0.0
    assert load_metric("fwdbwd_ms").read(ctx) > 0.0
    enc = load_metric("encode_kernel_roofline_pct").read(ctx)
    assert 0.0 < enc <= 100.0
    bd = ctx.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
