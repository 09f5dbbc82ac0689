"""The work the algorithm needs, counted from a configuration's shapes.

Counts are the algorithm's, not the implementation's: attention is causal
over the real prompt, a decode step reads the cache of each active slot's
real length (not ``max_seq``) and only the last prompt position is
unembedded.  A later change that reads less is judged against the same
work.  Norm scales and biases are left out (under 0.1% of either count).
Weights and cache are bfloat16 (2 bytes), as the configurations state.

The counts here are the dense Qwen2/Qwen3 decoder's.  A configuration
that names an architecture module (``bench/spec.py``
``load_architecture``) gets that module's function in place of each count
it defines; the others stay dense, and call the module's where they use a
count it replaced.  The dense function stays reachable as ``.dense``.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

from bench import spec

BYTES = 2  # bfloat16 weights and cache

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def by_architecture(dense):
    """A count the configuration's architecture module may replace with a
    function of the same name."""
    name = dense.__name__

    @functools.wraps(dense)
    def count(c: dict, *args):
        own = getattr(spec.architecture(c), name, None)
        return (own or dense)(c, *args)

    count.dense = dense
    return count


def dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"])


def layer_params(c: dict) -> int:
    """Matmul parameters of one layer: q, k, v, o and the gated MLP."""
    _, d, h, kv, hd, ff, _ = dims(c)
    return d * hd * (h + 2 * kv) + h * hd * d + 3 * d * ff


@by_architecture
def weight_bytes(c: dict) -> int:
    """Weights a whole-model pass reads: every layer and the output head
    (the input embedding contributes only its gathered rows)."""
    n_layers, d, *_, vocab = dims(c)
    return (n_layers * layer_params(c) + vocab * d) * BYTES


@by_architecture
def kv_bytes_per_token(c: dict) -> int:
    n_layers, _, _, kv, hd, _, _ = dims(c)
    return n_layers * 2 * kv * hd * BYTES


@by_architecture
def linear_flops_per_token(c: dict) -> int:
    return 2 * dims(c)[0] * layer_params(c)


def attn_flops(c: dict, n_keys: int) -> int:
    """QK^T and PV of one query over ``n_keys`` keys, every layer."""
    n_layers, _, h, _, hd, _, _ = dims(c)
    return n_layers * 4 * h * hd * n_keys


@by_architecture
def prefill_flops(c: dict, s: int) -> int:
    """One prompt of ``s`` tokens: causal attention, logits of the last
    position only."""
    n_layers, d, h, _, hd, _, vocab = dims(c)
    causal_pairs = s * (s + 1) // 2
    return (s * linear_flops_per_token(c)
            + n_layers * 4 * h * hd * causal_pairs + 2 * d * vocab)


@by_architecture
def prefill_bytes(c: dict, s: int) -> int:
    """Weights once, the prompt's embedding rows, the cache it writes."""
    d = dims(c)[1]
    return weight_bytes(c) + s * d * BYTES + s * kv_bytes_per_token(c)


@by_architecture
def decode_flops(c: dict, n_active: int, ctx_sum: int) -> int:
    """One step for ``n_active`` slots whose cached lengths sum to
    ``ctx_sum``: each attends over its cache plus the new token."""
    d, vocab = dims(c)[1], dims(c)[6]
    per_tok = linear_flops_per_token(c) + 2 * d * vocab
    return n_active * per_tok + attn_flops(c, ctx_sum + n_active)


@by_architecture
def decode_bytes(c: dict, n_active: int, ctx_sum: int) -> int:
    """Weights once, each active slot's cache at its real length, and the
    new token's cache entry."""
    d = dims(c)[1]
    return (weight_bytes(c) + n_active * d * BYTES
            + (ctx_sum + n_active) * kv_bytes_per_token(c))


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def peaks(kind: str) -> dict:
    """The peak table's row for JAX ``device_kind``; unknown kinds raise."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {_PEAKS.name} "
                       f"(have {sorted(table)}); add its published peaks")
    return table[kind]
