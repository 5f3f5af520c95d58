"""Plain decoder LM of the Qwen3 family: the reference for LM configurations.

Pre-norm decoder: RMSNorm, grouped-query attention (query head i reads
key/value head i // (heads / kv_heads)) with per-head RMSNorm on queries and
keys before rotary embeddings (rotate-half, base `rope_theta`), causal
softmax, then a SwiGLU MLP (silu(x W_gate) * (x W_up)) W_down, and a final
RMSNorm before the (tied) head.  The loss is the mean next-token cross
entropy.  Parameters arrive in the program's layout: `embed`, the layers
stacked along a leading axis under `super[0]`, `final_norm`, and `lm_head`
when the head is not tied.

Float32 math runs at `Precision.HIGHEST`.  Each layer is rematerialised and the head and loss
run in blocks of rows, so a 2048-token sequence of a 0.6B model fits one
chip beside the run's own state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 512  # rows of the head and loss computed at once


def make(config: dict):
    return _LM(config)


class _LM:
    def __init__(self, config: dict):
        self.h = config["num_attention_heads"]
        self.hkv = config["num_key_value_heads"]
        self.hd = config["head_dim"]
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.tied = bool(config["tie_word_embeddings"])
        self.loss_and_grad = jax.jit(jax.value_and_grad(self.loss))
        self._loss = jax.jit(self.loss)

    # -- pieces ------------------------------------------------------------

    @staticmethod
    def _mm(a, b):
        prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
        return jnp.matmul(a, b, precision=prec)

    def _rms(self, x, w):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)

    def _rope(self, x):
        T, half = x.shape[1], self.hd // 2
        freqs = 1.0 / (self.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs       # (T, half)
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _layer(self, x, p):
        B, T, _ = x.shape
        a = p["attn"]
        h = self._rms(x, p["ln1"])
        q = self._mm(h, a["wq"]).reshape(B, T, self.h, self.hd)
        k = self._mm(h, a["wk"]).reshape(B, T, self.hkv, self.hd)
        v = self._mm(h, a["wv"]).reshape(B, T, self.hkv, self.hd)
        q = self._rope(self._rms(q, a["q_norm"]))
        k = self._rope(self._rms(k, a["k_norm"]))
        g = self.h // self.hkv
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=prec).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(self.hd))
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhts,bshd->bthd", w, v, precision=prec).reshape(B, T, -1)
        x = x + self._mm(o, a["wo"])
        f = p["ffn"]
        h2 = self._rms(x, p["ln2"])
        up = jax.nn.silu(self._mm(h2, f["w_gate"])) * self._mm(h2, f["w_in"])
        return x + self._mm(up, f["w_out"])

    # -- model -------------------------------------------------------------

    def loss(self, params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        x = jnp.take(params["embed"], tokens, axis=0)
        layer = jax.checkpoint(lambda x, p: (self._layer(x, p), None))
        x, _ = jax.lax.scan(layer, x, params["super"][0])
        x = self._rms(x, params["final_norm"])
        head = params["embed"].T if self.tied else params["lm_head"]
        B, T, d = x.shape
        n = B * T
        rows = ROWS if n % ROWS == 0 else n
        xs = x.reshape(n // rows, rows, d)
        ys = labels.reshape(n // rows, rows)

        @jax.checkpoint
        def block(carry, xy):
            xb, yb = xy
            logits = self._mm(xb, head).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            return carry + jnp.sum(lse - picked), None

        total, _ = jax.lax.scan(block, jnp.float32(0.0), (xs, ys))
        return total / n

    def metric(self, params, eval_data) -> float:
        """Held-out perplexity: exp of the mean loss over the eval batches."""
        losses = [self._loss(params, {k: v[i] for k, v in eval_data.items()})
                  for i in range(len(eval_data["tokens"]))]
        return float(jnp.exp(jnp.mean(jnp.stack(losses))))

