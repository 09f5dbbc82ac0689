"""The plain reference: the configuration's forward pass in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, written from the
published architecture (Qwen2 / Qwen3 decoder: RMSNorm, rotary positions
on the two halves of each head, grouped-query causal attention, SwiGLU
MLP, optional q/k bias and q/k norm, tied or untied head).  It imports
nothing of the program.  Its weights are made here from the run's weight
seed by the same seeded recipe the served model is initialised with
(a normal draw per matrix, scaled by 1/sqrt(fan-in), stored in bfloat16;
biases 0, norm scales 1), so both hold the same bfloat16 values and the
reference computes with them in float32.

``Reference.gaps`` reads, at every served position, how far the served
token's logit lies below the reference's best.  With ``control=True`` the
same pass runs with every matmul operand rounded to float8 (e4m3, scaled
per row), the next precision below the configuration's bfloat16; the
control's reading is the reference's gap for the token the float8 pass
puts first.

These are the dense decoder's.  A configuration that names an
architecture module (``bench/spec.py`` ``load_architecture``) gets that
module's ``init_weights`` and ``forward_logits`` in ``Reference``; the
blocking, padding, gaps and control stay this file's.  A module may use
``fp8`` for its control.
"""

from __future__ import annotations

import numpy as np

SEQ_BLOCK = 1024   # sequences are padded to a multiple of this
Q_BLOCK = 512      # attention query rows per block
FP8_MAX = 448.0    # largest finite float8_e4m3fn


def _dims(c):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def init_weights(c: dict, seed: int):
    """The seeded bfloat16 weights (one jitted call on the device)."""
    import jax
    import jax.numpy as jnp

    n_layers, d, h, kv, hd, ff, vocab = _dims(c)
    bf16 = jnp.bfloat16

    def normal(key, shape, fan):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / (fan ** 0.5))).astype(bf16)

    def layer(key):
        k_attn, k_mlp = jax.random.split(key)
        ks = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        p = {"wq": normal(ks[0], (d, h, hd), d),
             "wk": normal(ks[1], (d, kv, hd), d),
             "wv": normal(ks[2], (d, kv, hd), d),
             "wo": normal(ks[3], (h, hd, d), h * hd),
             "w_gate": normal(km[0], (d, ff), d),
             "w_up": normal(km[1], (d, ff), d),
             "w_down": normal(km[2], (ff, d), ff),
             "ln1": jnp.ones((d,), jnp.float32),
             "ln2": jnp.ones((d,), jnp.float32)}
        if c["attention_bias"]:
            p.update(bq=jnp.zeros((h, hd), bf16), bk=jnp.zeros((kv, hd), bf16),
                     bv=jnp.zeros((kv, hd), bf16))
        if c["qk_norm"]:
            p.update(q_norm=jnp.ones((hd,), jnp.float32),
                     k_norm=jnp.ones((hd,), jnp.float32))
        return p

    def init(key):
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        w = {"embed": normal(k_emb, (vocab, d), d),
             "layers": jax.vmap(layer)(jax.random.split(k_layers, n_layers)),
             "final": jnp.ones((d,), jnp.float32)}
        if not c["tie_word_embeddings"]:
            w["head"] = normal(k_head, (vocab, d), vocab)
        return w

    return jax.jit(init)(jax.random.PRNGKey(seed))


def fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def forward_logits(w, tokens, pos, c: dict, control: bool):
    """Float32 logits at positions ``pos`` of one causal pass over
    ``tokens`` (1-D, padded at the end: padding never reaches a read
    position)."""
    import jax
    import jax.numpy as jnp

    n_layers, d, h, kv, hd, ff, vocab = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    f32 = jnp.float32
    s = tokens.shape[0]
    if control:
        def mm(spec, a, b, a_axis, b_axis):
            return jnp.einsum(spec, fp8(a, a_axis), fp8(b, b_axis))
    else:
        def mm(spec, a, b, a_axis, b_axis):
            return jnp.einsum(spec, a, b)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale

    inv = 1.0 / (theta ** (jnp.arange(hd // 2, dtype=f32) / (hd // 2)))
    ang = jnp.arange(s, dtype=f32)[:, None] * inv          # (s, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

    def rope(x):                                           # (s, heads, hd)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    group = h // kv

    def attend(q, k, v):
        """Causal grouped-query attention, in query blocks."""
        qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kv, group, hd)
        kpos = jnp.arange(s)

        def block(args):
            i, qi = args
            sc = mm("qkgd,skd->kgqs", qi, k, -1, -1) * (hd ** -0.5)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            sc = jnp.where((qpos[:, None] >= kpos[None, :])[None, None],
                           sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            return mm("kgqs,skd->qkgd", pr, v, -1, 0)

        out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
        return out.reshape(s, h, hd)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        y = rms(x, p["ln1"])
        q = mm("sd,dhk->shk", y, p["wq"], -1, 0)
        k = mm("sd,dhk->shk", y, p["wk"], -1, 0)
        v = mm("sd,dhk->shk", y, p["wv"], -1, 0)
        if c["attention_bias"]:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        if c["qk_norm"]:
            q, k = rms(q, p["q_norm"]), rms(k, p["k_norm"])
        x = x + mm("shk,hkd->sd", attend(rope(q), rope(k), v), p["wo"],
                   (-2, -1), (0, 1))
        y = rms(x, p["ln2"])
        g = mm("sd,df->sf", y, p["w_gate"], -1, 0)
        u = mm("sd,df->sf", y, p["w_up"], -1, 0)
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], -1, 0)
        return x, None

    x = w["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = rms(x, w["final"])[pos]
    head = w.get("head", w["embed"]).astype(f32)
    return mm("pd,vd->pv", x, head, -1, -1)


class Reference:
    """The reference on this process's default device (the head runs it
    after every replica has exited, so the chip is free), of the
    architecture the configuration names."""

    def __init__(self, c: dict, seed: int):
        import jax

        from bench import spec

        arch = spec.architecture(c)
        self.init_weights = init_weights if arch is None else arch.init_weights
        self.forward_logits = (forward_logits if arch is None
                               else arch.forward_logits)
        jax.config.update("jax_default_matmul_precision", "highest")
        self.c = c
        self.w = self.init_weights(c, seed)
        self._fns = {}

    def _fn(self, control: bool):
        import jax
        import jax.numpy as jnp

        if control not in self._fns:
            c, forward_logits = self.c, self.forward_logits

            def run(w, tokens, pos, served):
                logits = forward_logits(w, tokens, pos, c, False)
                best = logits.max(-1)

                def gap(tok):
                    return best - jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]

                out = {"served": gap(served)}
                if control:
                    out["control"] = gap(
                        forward_logits(w, tokens, pos, c, True).argmax(-1))
                return out

            self._fns[control] = jax.jit(run)
        return self._fns[control]

    def gaps(self, prompt, served, *, control: bool = False,
             n_pos: int) -> dict:
        """Per served position, the reference's best logit minus the served
        token's (``served``) and, with ``control``, minus the float8 pass's
        first choice's (``control``).  ``n_pos`` fixes the shape of the
        position vector (the mix's longest output), so every call of a
        cell compiles once per padded length."""
        served = np.asarray(served, np.int32)
        n = len(served)
        total = len(prompt) + n - 1
        s = -(-total // SEQ_BLOCK) * SEQ_BLOCK
        tokens = np.zeros(s, np.int32)
        tokens[:len(prompt)] = prompt
        tokens[len(prompt):total] = served[:-1]
        pos = np.zeros(n_pos, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        want = np.zeros(n_pos, np.int32)
        want[:n] = served
        out = self._fn(control)(self.w, tokens, pos, want)
        return {k: np.asarray(v, np.float64)[:n] for k, v in out.items()}
