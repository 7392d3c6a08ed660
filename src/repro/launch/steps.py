"""Jittable train_step / serve_step factories shared by the real drivers
(launch/train.py, launch/serve.py) and the multi-pod dry-run.

train_step: microbatched grad accumulation + chunked cross-entropy (the
LM-head matmul and loss run over sequence chunks so the (B, S, vocab)
logits tensor is never materialized — with 256k-entry vocabularies that
tensor would dwarf everything else in HBM).

serve_step: one decode iteration for a batch of sequences against the KV
cache (the iteration-level batching engine calls this once per iteration).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models import encdec as ED
from repro.models.config import ModelConfig
from repro.training.optimizer import AdamWState, adamw_update, cosine_lr


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def chunked_ce_loss(hidden: jnp.ndarray, head: jnp.ndarray,
                    labels: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy over sequence chunks.  hidden: (B, S, d) post-norm;
    head: (d, V); labels: (B, S).  fp32 log-softmax.

    Memory discipline:
      * the gold logit is h . head[:, label] computed via ONE gather of the
        label rows (same pattern as the forward embedding lookup) + a dot —
        never a (B, c, V) one-hot or take_along_axis over the vocab-sharded
        logits (both force GSPMD replication, ~10-20 GB/device);
      * only the logsumexp term touches (B, c, V), one chunk at a time,
        sharded along the vocab axis.
    """
    B, S, d = hidden.shape
    # gold logits for ALL positions with one vocab gather
    lab_vec = head.T[labels]                              # (B, S, d)
    gold_all = jnp.einsum("bsd,bsd->bs", hidden.astype(jnp.float32),
                          lab_vec.astype(jnp.float32))

    c = min(chunk, S)
    S_p = -(-S // c) * c
    if S_p != S:
        hidden = jnp.pad(hidden, ((0, 0), (0, S_p - S), (0, 0)))
    nc = S_p // c
    hs = hidden.reshape(B, nc, c, d).transpose(1, 0, 2, 3)

    def chunk_lse(carry, h):
        logits = (h @ head).astype(jnp.float32)           # (B, c, V)
        return carry + jnp.sum(jax.nn.logsumexp(logits, axis=-1)), None

    lse_total, _ = jax.lax.scan(chunk_lse, jnp.zeros((), jnp.float32), hs)
    # padded positions contribute logsumexp of the zero-vector hidden —
    # a constant log(V) offset; subtract it exactly.
    n_pad = S_p - S
    if n_pad:
        pad_lse = jax.nn.logsumexp(
            jnp.zeros((head.shape[1],), jnp.float32))
        lse_total = lse_total - B * n_pad * pad_lse
    return (lse_total - jnp.sum(gold_all)) / (B * S)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, microbatches: int = 1,
                    remat: bool = True, peak_lr: float = 3e-4,
                    loss_chunk: int = 512):
    """Returns train_step(params, opt_state, batch) ->
    (params, opt_state, metrics).

    batch: {"tokens": (B, S) int32, "labels": (B, S) int32} for LM archs;
    {"frames": (B, Ssrc, d), "tokens", "labels"} for enc-dec;
    {"embeds": (B, S, d), "labels"} for stub-frontend archs.
    """

    def loss_fn(params, batch):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["head"])
        if cfg.encoder is not None:
            memory = ED.encode(params, cfg, batch["frames"], remat=remat)
            hidden = T.forward(params, cfg, tokens=batch["tokens"],
                               enc_memory=memory, remat=remat,
                               return_hidden=True)
        elif cfg.embeds_input:
            hidden = T.forward(params, cfg, embeds=batch["embeds"],
                               remat=remat, return_hidden=True)
        else:
            hidden = T.forward(params, cfg, tokens=batch["tokens"],
                               remat=remat, return_hidden=True)
        return chunked_ce_loss(hidden, head, batch["labels"],
                               chunk=loss_chunk)

    grad_fn = jax.value_and_grad(loss_fn)

    def train_step(params, opt_state: AdamWState, batch):
        if microbatches > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches,
                                 *x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def mb_step(acc, mb):
                loss_acc, grads_acc = acc
                loss, grads = grad_fn(params, mb)
                grads = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32),
                    grads_acc, grads)
                return (loss_acc + loss, grads), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                mb_step, (jnp.zeros((), jnp.float32), zero), mbs)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            loss, grads = grad_fn(params, batch)

        lr = cosine_lr(opt_state.step + 1, peak_lr=peak_lr)
        new_params, new_state, metrics = adamw_update(
            params, grads, opt_state, lr)
        metrics["loss"] = loss
        return new_params, new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# serve step
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig):
    """Returns serve_step(params, tokens (B,1), cache) ->
    (next_tokens (B,), cache) — greedy decode of one iteration."""

    def serve_step(params, tokens, cache):
        logits, cache = T.decode_step(params, cfg, tokens, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return serve_step
