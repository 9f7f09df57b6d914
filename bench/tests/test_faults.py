"""A run with the timed path broken underneath must come out not correct.

The harness runs as on the chip (set-up, checked steps, window, reference,
comparison) but on CPU devices at a tiny size, with the look for a chip
skipped.  Each fault a training cell can have is planted in the program:
a step that returns its state unchanged, half of each peer's batch left
out with the mean taken over the rest, and the exchange between chips
left out; in the codec cell also a truncation level collapsed to a fiftieth,
which error feedback hides from the synced input but not from the synced
gradient.  The sound run of the same tiny cell, under the same limits,
must come out correct.
"""
import json
from pathlib import Path

import jax
import pytest

from bench import harness
from bench.reference import half_batch_labels

DATA = Path(__file__).resolve().parent / "data"
OPT = {"name": "momentum_sgd", "lr": 0.05, "momentum": 0.9, "weight_decay": 5e-4}


def tiny_qwen():
    config = json.loads((DATA / "tiny-qwen2-vl.json").read_text())
    workload = {"name": "tiny.dp4.dsgd", "config": config["name"], "chips": 4, "mesh": "4",
                "sync": "dsgd", "method": "dsgd", "ef": False, "batch": 2, "seq": 64,
                "bucket_mb": 0.01, "optimizer": OPT,
                # sound runs read 1e-4, 6e-4, 6e-4 at this size, and peers agree
                "limits": {"loss_gap": 0.002, "grad_norm_gap": 0.01, "update_norm_gap": 0.01,
                           "replica_gap": 0.0}}
    return config, workload


def tiny_mamba():
    config = json.loads((DATA / "tiny-mamba2.json").read_text())
    workload = {"name": "tiny.1chip.faithful", "config": config["name"], "chips": 1,
                "mesh": "1", "sync": "faithful", "method": "tnqsgd", "bits": 3, "ef": True,
                "batch": 1, "seq": 128, "bucket_mb": 0.01, "optimizer": OPT,
                # sound runs read up to 4e-4, 5e-3, 0.28 and 0.27 at this size
                "limits": {"loss0_gap": 0.002, "sync_in_grad_gap": 0.02,
                           "grad_norm_gap": 0.55, "update_norm_gap": 0.5}}
    return config, workload


def run(cell, hook=None):
    jax.config.update("jax_enable_compilation_cache", False)
    return harness.run(["--workload", cell[1]["name"], "--seed", "3000000017",
                        "--seconds", "0.5", "--trace", "0"],
                       require_chip=False, cell=cell, program_hook=hook)


def keep_state(program):
    inner, n = program.step_fn, (3 if program.ef else 2)
    program.step_fn = jax.jit(lambda *a: (*a[:n], inner(*a)[-1]))


def half_batch(program):
    inner, peers = program.step_fn, program.peers

    def step(*a):
        batch = a[-2]
        lab = batch.labels.reshape(peers, -1, batch.labels.shape[-1])
        lab = jax.vmap(half_batch_labels)(lab).reshape(batch.labels.shape)
        return inner(*a[:-2], batch._replace(labels=lab), a[-1])

    program.step_fn = jax.jit(step)


@pytest.mark.parametrize("make", [tiny_qwen, tiny_mamba], ids=["qwen-dp4", "mamba-1chip"])
def test_sound_run_is_correct(make):
    res = run(make())
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("make", [tiny_qwen, tiny_mamba], ids=["qwen-dp4", "mamba-1chip"])
@pytest.mark.parametrize("fault", [keep_state, half_batch], ids=["state-unchanged", "half-batch"])
def test_planted_fault_is_not_correct(make, fault):
    res = run(make(), fault)
    assert not res["correct"], res["checks"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    # every peer keeps its own gradient: the mean over peers is never taken
    monkeypatch.setattr(jax.lax, "pmean", lambda x, axis_name, **kw: x)
    res = run(tiny_qwen())
    assert not res["correct"], res["checks"]
    assert res["checks"]["replica_gap"]["value"] > 0.0


def test_collapsed_truncation_is_not_correct(monkeypatch):
    # every bucket's codebook shrunk to a fiftieth: the codec sends about 2 %
    # of each gradient and parks the rest in the EF residual
    from repro.core import codecs
    from repro.core.quantizers import QuantMeta

    plan = codecs.plan_from_stats

    def collapsed(*a, **kw):
        meta = plan(*a, **kw)
        return QuantMeta(levels=meta.levels / 50, alpha=meta.alpha / 50)

    monkeypatch.setattr(codecs, "plan_from_stats", collapsed)
    res = run(tiny_mamba())
    assert not res["correct"], res["checks"]
    assert res["checks"]["sync_in_grad_gap"]["value"] <= 0.02
    assert res["checks"]["grad_norm_gap"]["value"] > 0.55
