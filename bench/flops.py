"""Operations and bytes that the mathematics needs, from shapes alone.

Counts are model operations: two per multiply-add, each product counted once
(no recomputation, no padding, no masked-out work). ``arch`` holds the
configuration file's keys (``hidden_size``, ``intermediate_size`` ...).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The device's peaks; a device missing from the table is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def _dims(arch: dict):
    return (arch["hidden_size"], arch["num_attention_heads"],
            arch["num_key_value_heads"], arch["head_dim"],
            arch["intermediate_size"], arch["vocab_size"],
            arch["num_hidden_layers"])


def dense_per_token(arch: dict) -> int:
    """One token's projections and MLP, over all layers (no attention
    scores, no output head)."""
    D, H, K, hd, F, _, L = _dims(arch)
    qkvo = 2 * D * (H * hd) + 2 * 2 * D * (K * hd) + 2 * (H * hd) * D
    mlp = 3 * 2 * D * F
    return L * (qkvo + mlp)


def head(arch: dict) -> int:
    D, *_ = _dims(arch)
    return 2 * D * arch["vocab_size"]


def attention_scores(arch: dict, pairs: int) -> int:
    """q·k and p·v over ``pairs`` (query, key) pairs, all layers."""
    _, H, _, hd, _, _, L = _dims(arch)
    return L * 2 * 2 * H * hd * pairs


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def forward(arch: dict, seq_len: int, logits_rows: int) -> int:
    """A causal forward over one sequence, with the output head on
    ``logits_rows`` of its positions."""
    return (seq_len * dense_per_token(arch)
            + attention_scores(arch, causal_pairs(seq_len))
            + logits_rows * head(arch))


def train_step(arch: dict, batch: int, seq_len: int) -> int:
    """Forward and backward (twice the forward) over every row."""
    return 3 * batch * forward(arch, seq_len, seq_len)


def prefill(arch: dict, seq_len: int) -> int:
    """One prompt's prefill: every position, the head on the last one."""
    return forward(arch, seq_len, 1)


def decode_step(arch: dict, contexts: Iterable[int]) -> int:
    """One batched decode step; ``contexts`` gives, for each slot that holds
    a request, how many positions its new token attends to."""
    ctx = list(contexts)
    return (len(ctx) * (dense_per_token(arch) + head(arch))
            + attention_scores(arch, sum(ctx)))


# ------------------------------------------------------------- flash kernel
def flash_forward(batch: int, heads: int, kv_heads: int, seq_len: int,
                  head_dim: int, itemsize: int = 2) -> dict:
    """The causal flash forward over [batch, heads, seq_len, head_dim]:
    operations of q·k and p·v over the causal pairs, and the bytes of q, k,
    v and o in their dtype plus the float32 log-sum-exp it returns."""
    ops = 2 * 2 * batch * heads * head_dim * causal_pairs(seq_len)
    qo = 2 * batch * heads * seq_len * head_dim * itemsize
    kv = 2 * batch * kv_heads * seq_len * head_dim * itemsize
    lse = batch * heads * seq_len * 4
    return {"ops": ops, "bytes": qo + kv + lse}


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> dict:
    """The least time the chip could take over the time taken, and which
    bound sets that least time."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"share": max(t_ops, t_bytes) / seconds,
            "bound": "compute" if t_ops >= t_bytes else "memory"}


# ------------------------------------------------------- per-layer readings
def program_mfu(obs: dict, tag: str):
    """Model operations of the ``tag`` programs traced, over their device
    time at the chip's peak bf16 rate, in percent; None when no such
    program ran in the traced window. ``obs`` is what a driver observed:
    the trace's summary, the chip's peaks and the work it counted."""
    prog = obs.get("trace", {}).get("programs", {}).get(tag)
    work = obs.get("work", {}).get(tag)
    if not prog or not work or prog["seconds"] <= 0 or not work["flops"]:
        return None
    return 100.0 * work["flops"] / (prog["seconds"]
                                    * obs["peak"]["bf16_flops_per_s"])


def kernel_roofline(obs: dict, tag: str):
    """The ``tag`` kernel's share of its roofline over the device time of its
    events, in percent; None when the kernel did not run there."""
    k = obs.get("trace", {}).get("kernels", {}).get(tag)
    work = obs.get("work", {}).get(tag)
    if not k or not work or k["seconds"] <= 0 or not k["count"]:
        return None
    return 100.0 * roofline_share(work["ops"], work["bytes"], k["seconds"],
                                  obs["peak"])["share"]
