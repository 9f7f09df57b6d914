"""Model FLOPs a training step requires, from the configuration's shapes.

Forward FLOPs per sequence, times three for forward and backward; nothing
recomputed (remat) counts, nor masked work: causal attention and the
SSD's intra-chunk terms count their lower triangle only, and the LM head
counts labelled positions only.  Elementwise work (norms, activations,
rotary, softmax) is left out, as is usual for model FLOP/s utilisation.

A family is found by the configuration's ``reference`` name: a function
of that name here, or ``per_sequence`` in ``bench/flops_<name>.py``.
"""
from __future__ import annotations

import importlib


def transformer(config: dict, seq: int) -> float:
    """GQA + SwiGLU decoder, tied head, optional projected image patches."""
    pub, red = config["published"], config.get("reduced", {})
    d = pub["hidden_size"]
    heads, kv = pub["num_attention_heads"], pub["num_key_value_heads"]
    hd = d // heads
    f = pub["intermediate_size"]
    vocab = pub["vocab_size"]
    layers = red.get("num_hidden_layers", pub["num_hidden_layers"])
    sizes = config.get("assumed_sizes", {})
    patches, vdim = sizes.get("vlm_patches", 0), sizes.get("vision_dim", 0)
    per_pos = 2 * d * heads * hd + 2 * 2 * d * kv * hd + 2 * heads * hd * d + 3 * 2 * d * f
    causal_pairs = seq * (seq + 1) / 2
    attention = 2 * 2 * heads * hd * causal_pairs
    layer = per_pos * seq + attention
    projector = 2 * vdim * d * patches
    head = 2 * d * vocab * (seq - patches - 1)
    return float(layers * layer + projector + head)


def mamba2(config: dict, seq: int) -> float:
    """Mamba-2 blocks evaluated by the chunked SSD, tied head."""
    pub, red = config["published"], config.get("reduced", {})
    ssm = pub["ssm_cfg"]
    d = pub["d_model"]
    layers = red.get("n_layer", pub["n_layer"])
    n, w, p = ssm["d_state"], ssm["d_conv"], ssm["headdim"]
    inner = ssm["expand"] * d
    h = inner // p
    q = ssm["chunk_size"]
    g = ssm.get("ngroups", 1)
    vocab = config.get("assumed_sizes", {}).get("vocab_size", pub.get("vocab_size"))
    in_proj = 2 * d * (2 * inner + 2 * g * n + h)
    conv = 2 * w * (inner + 2 * g * n)
    out_proj = 2 * inner * d
    tri = (q + 1) / 2                      # mean causal span inside a chunk
    ssd = 2 * g * n * tri + 2 * h * p * tri + 2 * n * h * p + 2 * n * h * p
    layer = (in_proj + conv + out_proj + ssd) * seq
    head = 2 * d * vocab * (seq - 1)
    return float(layers * layer + head)


def per_sequence(config: dict, seq: int) -> float:
    """Forward FLOPs of one sequence of ``seq`` positions."""
    name = config["reference"]
    fn = globals().get(name)
    if fn is None:
        fn = importlib.import_module(f"bench.flops_{name}").per_sequence
    return fn(config, seq)


def data_peers(workload: dict) -> int:
    """Data peers of a mesh spec: 'd', 'd x model' or 'pod x d x model'."""
    dims = [int(x) for x in str(workload["mesh"]).split("x")]
    return dims[0] * (dims[1] if len(dims) == 3 else 1)


def per_step(config: dict, workload: dict) -> float:
    """Forward and backward FLOPs of one step over every data peer's rows."""
    rows = data_peers(workload) * int(workload["batch"])
    return 3.0 * rows * per_sequence(config, int(workload["seq"]))
