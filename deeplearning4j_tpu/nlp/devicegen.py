"""Device-side skip-gram example generation — train from the CORPUS, not
from shipped pair batches.

Why: the host->device link bounds word2vec when it is slow. Shipping
(input, target, mask) pair batches costs ~50 bytes/word (rounds 4-5
measured ~2.8 MB/s effective on the link of that time -> a hard ~45k
words/s ceiling regardless of device speed; not re-measured on the
present machine); shipping the INDEXED CORPUS costs 4 bytes/word. So the host uploads each epoch's subsampled corpus once (one
int32 per surviving word, sentences separated by `window` sentinel
tokens) and the device does everything the reference's
VectorCalculationsThread workers did host-side
(SequenceVectors.java:285-289, SkipGram.java:271): dynamic windowing,
pair extraction, negative sampling, and the table updates — one jitted
dispatch per epoch.

Semantics preserved (word2vec.c / reference parity):
- dynamic window: per center, effective window = window - b with
  b ~ U[0, window) — pairs at distance 1 are always trained.
- skip-gram trains input = CONTEXT word, output = center word.
- sentence boundaries: a `window`-wide sentinel gap guarantees any
  (center, context) pair within `window` distance that crosses a
  boundary touches a sentinel and is masked out.
- lr decays linearly over PAIRS ACTUALLY TRAINED (carried through the
  scan) toward min_lr — word2vec.c's decay-by-progress, measured on
  true pair counts instead of the host path's expected-pairs estimate.

The update math is learning.py's `_build_update` body (same trust-region
scatter updates, same device-side negative sampling), fed from in-kernel
generated batches.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.learning import _build_update

SENTINEL = -1


def pack_corpus(sentences: List[np.ndarray], window: int,
                bucket: int = 8192) -> np.ndarray:
    """Concatenate indexed sentences into one int32 array with `window`
    SENTINEL tokens between (and after) them, padded with SENTINEL up to
    the next power-of-two multiple of `bucket`: corpora within 2x of each
    other share one compiled program (per-epoch subsampling jitter never
    recompiles; a growing corpus recompiles only on doubling)."""
    gap = np.full(window, SENTINEL, np.int32)
    parts = []
    for s in sentences:
        if s.size == 0:
            continue
        parts.append(s.astype(np.int32))
        parts.append(gap)
    flat = (np.concatenate(parts) if parts
            else np.zeros(0, np.int32))
    size = int(bucket)
    while size < flat.size:
        size *= 2
    if size != flat.size:
        flat = np.concatenate(
            [flat, np.full(size - flat.size, SENTINEL, np.int32)])
    return flat


def _chunk_pairs(corpus, start, n_centers, window, key):
    """Extract the (input=context, target=center, valid) pair block for
    centers at positions [start, start+n_centers). Shapes are static:
    [n_centers * 2 * window] flattened pairs."""
    T = corpus.shape[0]
    c_pos = start + jnp.arange(n_centers)
    center = corpus[jnp.clip(c_pos, 0, T - 1)]
    # dynamic window (word2vec.c: b = next_random % window)
    b = jax.random.randint(key, (n_centers,), 0, window)
    w_eff = window - b                                   # [n_centers]
    offsets = jnp.concatenate(
        [jnp.arange(-window, 0), jnp.arange(1, window + 1)])  # [2W]
    ctx_pos = c_pos[:, None] + offsets[None, :]          # [n_centers, 2W]
    in_bounds = (ctx_pos >= 0) & (ctx_pos < T)
    ctx = corpus[jnp.clip(ctx_pos, 0, T - 1)]
    valid = (
        in_bounds
        & (center[:, None] >= 0)
        & (ctx >= 0)
        & (jnp.abs(offsets)[None, :] <= w_eff[:, None])
    )
    return (ctx.reshape(-1), jnp.repeat(center, 2 * window),
            valid.reshape(-1))


def corpus_pairs_debug(corpus, window, key, n_centers=None):
    """Test hook: the full pair list one chunk would generate (host
    array outputs)."""
    n = int(n_centers if n_centers is not None else corpus.shape[0])
    ins, tgt, valid = _chunk_pairs(jnp.asarray(corpus, jnp.int32), 0, n,
                                   int(window), key)
    return (np.asarray(ins), np.asarray(tgt),
            np.asarray(valid).astype(bool))


def make_corpus_skipgram_step(*, negative: int, window: int,
                              pairs_per_batch: int = 8192,
                              max_row_update: float = 0.25):
    """Jitted one-dispatch-per-epoch skip-gram trainer.

    step(syn0, syn1neg, unigram, corpus, lr0, min_lr, total_pairs,
         seen0, key) -> (syn0, syn1neg, mean_loss, seen)

    The scan walks the corpus in center chunks of
    pairs_per_batch // (2*window) positions; each chunk trains its
    (<= pairs_per_batch) generated pairs through learning.py's update
    body with the lr for the pairs seen so far.
    """
    body = _build_update(use_hs=False, negative=negative, with_doc=False,
                         train_words=True, max_row_update=max_row_update)
    n_centers = max(1, pairs_per_batch // (2 * window))

    def step(syn0, syn1neg, unigram, corpus, lr0, min_lr, total_pairs,
             seen0, key):
        T = corpus.shape[0]
        n_chunks = -(-T // n_centers)
        dummy_syn1 = jnp.zeros((1, syn0.shape[1]), syn0.dtype)
        dummy_doc = jnp.zeros((1, syn0.shape[1]), syn0.dtype)

        def one(carry, inp):
            s0, s1n, seen = carry
            i, k = inp
            k_win, k_neg = jax.random.split(k)
            ins, tgt, valid = _chunk_pairs(
                corpus, i * n_centers, n_centers, window, k_win)
            batch = {
                "h_idx": jnp.maximum(ins, 0)[:, None].astype(jnp.int32),
                "row_mask": valid,
                "pos": jnp.maximum(tgt, 0).astype(jnp.int32),
            }
            lr = jnp.maximum(lr0 * (1.0 - seen / total_pairs), min_lr)
            s0, _, s1n, _, loss = body(
                s0, dummy_syn1, s1n, dummy_doc, unigram, batch, lr, k_neg)
            # seen carried in f32: still exact (+<=8192 per chunk) far past
            # int32 range, and it only feeds the lr ramp
            n_valid = jnp.sum(valid.astype(jnp.float32))
            seen = seen + n_valid
            return (s0, s1n, seen), (loss, n_valid)

        keys = jax.random.split(key, n_chunks)
        (syn0, syn1neg, seen), (losses, weights) = jax.lax.scan(
            one, (syn0, syn1neg, seen0),
            (jnp.arange(n_chunks), keys))
        # pair-weighted mean: bucket-padding chunks (0 valid pairs, loss 0)
        # must not dilute the reported epoch loss
        mean_loss = (jnp.sum(losses * weights)
                     / jnp.maximum(jnp.sum(weights), 1.0))
        return syn0, syn1neg, mean_loss, seen

    return jax.jit(step, donate_argnums=(0, 1))
