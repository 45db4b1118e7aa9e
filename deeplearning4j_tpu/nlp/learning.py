"""Device-side embedding training steps.

The AggregateSkipGram analog (reference:
models/embeddings/learning/impl/elements/SkipGram.java:271 batches pair
updates into native libnd4j aggregate ops; CBOW.java likewise). Here one
jitted XLA step consumes a BATCH of examples with static shapes:

  hidden  = mean of gathered syn0 rows (skip-gram: the one input word;
            CBOW/DM: the window, mask-padded; DM/DBOW add a doc row)
  outputs = hierarchical-softmax nodes (points/codes, mask-padded to the
            Huffman max code length) and/or negative samples
  update  = sigmoid-gradient scatter-adds into syn0/syn1/syn1neg/doc

All four tables are donated, so training runs in place on device. The
returned loss is the masked mean negative log sigmoid — the same quantity
the reference's inner loop accumulates.

Batching semantics: the reference applies pair updates SEQUENTIALLY (the
native aggregate loop), so a word hit N times in a batch sees N staged
updates of compounding freshness. A batched scatter-ADD applies all N
against the same stale row — equivalent for small lr*N, but a hot row
(small vocab x large batch) can see an effective rate of lr*N and
diverge. Updates are therefore summed and then TRUST-REGION CLIPPED per
destination row (norm cap), which preserves the sequential frequency
signal while bounding any single step's movement.

Design note (TPU): gathers/scatter-adds are HBM-bandwidth-bound; batching
thousands of examples per step amortizes dispatch exactly like the
reference's aggregate batching amortizes JNI, and XLA fuses the gate math
between them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _build_update(*, use_hs: bool, negative: int, with_doc: bool,
                  train_words: bool, max_row_update: float):
    """The un-jitted update body shared by the single-batch step and the
    scanned multi-batch step."""

    def _scatter_clipped(table, idx, delta, weights):
        """table[idx] += delta (summed over duplicate rows), each row's
        total clipped to max_row_update (weights: 1/0 per slot)."""
        d = delta * weights[:, None]
        acc = jnp.zeros_like(table).at[idx].add(d)
        norm = jnp.linalg.norm(acc, axis=1, keepdims=True)
        scale = jnp.minimum(1.0, max_row_update / jnp.maximum(norm, 1e-12))
        return table + acc * scale

    def step(syn0, syn1, syn1neg, doc, unigram, batch, lr, key):
        h_idx = batch["h_idx"]        # [B, C] rows of syn0
        B = h_idx.shape[0]
        dt = syn0.dtype
        # h_mask may be omitted (skip-gram: always exactly one input row);
        # padded tail rows are already no-ops via row_mask
        if "h_mask" in batch:
            hm = batch["h_mask"].astype(dt)
        else:
            hm = jnp.ones(h_idx.shape, dt)
        rm = batch["row_mask"].astype(dt)  # [B] 0 for padded tail rows

        rows = syn0[h_idx]                              # [B, C, D]
        cnt = jnp.sum(hm, axis=1, keepdims=True)        # [B, 1]
        h = jnp.sum(rows * hm[..., None], axis=1)       # [B, D]
        if with_doc:
            d_idx = batch["doc_idx"]                    # [B]
            h = h + doc[d_idx]
            cnt = cnt + 1.0
        h = h / jnp.maximum(cnt, 1.0)

        neu1e = jnp.zeros_like(h)
        loss = jnp.zeros((), dt)
        denom = jnp.zeros((), dt)

        if use_hs:
            points = batch["points"]                    # [B, L] rows of syn1
            codes = batch["codes"].astype(dt)           # [B, L] 0/1
            om = batch["hs_mask"].astype(dt) * rm[:, None]  # [B, L]
            u = syn1[points]                            # [B, L, D]
            logit = jnp.einsum("bd,bld->bl", h, u)
            label = 1.0 - codes
            p = jax.nn.sigmoid(logit)
            g = (label - p) * om                        # [B, L] raw gradient
            neu1e = neu1e + jnp.einsum("bl,bld->bd", g, u) * lr
            delta = (g * lr)[..., None] * h[:, None, :]  # [B, L, D]
            if train_words:
                syn1 = _scatter_clipped(
                    syn1, points.reshape(-1),
                    delta.reshape(-1, delta.shape[-1]), om.reshape(-1),
                )
            z = (2.0 * label - 1.0) * logit
            loss = loss + jnp.sum(-jax.nn.log_sigmoid(z) * om)
            denom = denom + jnp.sum(om)

        if negative > 0:
            pos = batch["pos"]                          # [B]
            if "neg" in batch:
                neg = batch["neg"]                      # [B, K]
            else:
                # device-side sampling from the resident unigram table —
                # saves shipping K int32 per example over the host link
                r = jax.random.randint(
                    key, (B, negative), 0, unigram.shape[0]
                )
                neg = unigram[r]
            idx = jnp.concatenate([pos[:, None], neg], axis=1)  # [B, 1+K]
            labels = jnp.zeros((B, 1 + negative), dt).at[:, 0].set(1.0)
            # a sampled negative that collides with the target is skipped
            # (word2vec.c: `if (target == word) continue`)
            om = jnp.concatenate(
                [jnp.ones((B, 1), dt),
                 (neg != pos[:, None]).astype(dt)], axis=1,
            ) * rm[:, None]
            u = syn1neg[idx]                            # [B, 1+K, D]
            logit = jnp.einsum("bd,bkd->bk", h, u)
            p = jax.nn.sigmoid(logit)
            g = (labels - p) * om
            neu1e = neu1e + jnp.einsum("bk,bkd->bd", g, u) * lr
            delta = (g * lr)[..., None] * h[:, None, :]
            if train_words:
                syn1neg = _scatter_clipped(
                    syn1neg, idx.reshape(-1),
                    delta.reshape(-1, delta.shape[-1]), om.reshape(-1),
                )
            z = (2.0 * labels - 1.0) * logit
            loss = loss + jnp.sum(-jax.nn.log_sigmoid(z) * om)
            denom = denom + jnp.sum(om)

        if train_words:
            upd = jnp.broadcast_to(
                neu1e[:, None, :], (B, h_idx.shape[1], neu1e.shape[-1])
            )
            syn0 = _scatter_clipped(
                syn0, h_idx.reshape(-1),
                upd.reshape(-1, upd.shape[-1]), hm.reshape(-1),
            )
        if with_doc:
            # doc rows keep SUM semantics (sequential-SGD equivalent): a
            # doc appears at most doc-length times per batch, so the
            # summed update is bounded by lr * len — no hot-row blowup,
            # and the aggregate signal is what makes doc vectors move
            doc = doc.at[batch["doc_idx"]].add(neu1e * rm[:, None])
        return syn0, syn1, syn1neg, doc, loss / jnp.maximum(denom, 1.0)

    return step


def make_embedding_step(*, use_hs: bool, negative: int, with_doc: bool,
                        train_words: bool = True, donate: bool = True,
                        max_row_update: float = 0.25):
    """Jitted single-batch update step. Static config: which output
    objective (HS and/or negative sampling), whether a doc row joins the
    hidden mean, and whether word tables train (False for infer_vector).
    max_row_update caps the 2-norm any single row moves per step."""
    body = _build_update(
        use_hs=use_hs, negative=negative, with_doc=with_doc,
        train_words=train_words, max_row_update=max_row_update,
    )

    def step(syn0, syn1, syn1neg, doc, batch, lr, unigram=None, key=None):
        if unigram is None:
            unigram = jnp.zeros((1,), jnp.int32)
        if key is None:
            key = jax.random.PRNGKey(0)
        return body(syn0, syn1, syn1neg, doc, unigram, batch, lr, key)

    donate_argnums = (0, 1, 2, 3) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_embedding_scan_step(*, use_hs: bool, negative: int, with_doc: bool,
                             train_words: bool = True, donate: bool = True,
                             max_row_update: float = 0.25):
    """Jitted MULTI-batch step: lax.scan the update over a stacked group
    of batches ([S, B, ...] leading axis) in ONE device call. Dispatch
    latency is paid
    once per group instead of once per batch — the host<->device analog
    of the reference batching JNI calls into aggregate ops."""
    body = _build_update(
        use_hs=use_hs, negative=negative, with_doc=with_doc,
        train_words=train_words, max_row_update=max_row_update,
    )

    def scan_step(syn0, syn1, syn1neg, doc, unigram, batches, lrs, key):
        keys = jax.random.split(key, lrs.shape[0])

        def one(carry, inp):
            s0, s1, s1n, d = carry
            batch, lr, k = inp
            s0, s1, s1n, d, loss = body(s0, s1, s1n, d, unigram, batch, lr, k)
            return (s0, s1, s1n, d), loss

        (syn0, syn1, syn1neg, doc), losses = jax.lax.scan(
            one, (syn0, syn1, syn1neg, doc), (batches, lrs, keys)
        )
        return syn0, syn1, syn1neg, doc, jnp.mean(losses)

    donate_argnums = (0, 1, 2, 3) if donate else ()
    return jax.jit(scan_step, donate_argnums=donate_argnums)
