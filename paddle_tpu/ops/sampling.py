"""The greedy head of the serving step programs.

Every jitted paged step (``models/llama.py`` and ``models/deepseek.py``
``_jitted_paged_step``; Llama's verify step inside itself) ends in this one
function, so that a token and a flag a row leave the device where the
float32 logits of the whole vocabulary did. The engine reads nothing else
of a step's head (``inference/engine.py`` ``_commit_rows``).
"""
from __future__ import annotations

import jax.numpy as jnp


def greedy_head(logits):
    """``logits`` f32[..., V] -> (token i32[...], finite bool[...]): the
    first index of the maximum along the vocabulary, as ``np.argmax`` picks
    it (a NaN counts as the maximum in both, so a poisoned row's token is
    the same on either side), and whether every logit of the row is finite
    (the engine's poison screen)."""
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.isfinite(logits).all(axis=-1))


def sampled(out, n_heads: int):
    """A step's outputs with the greedy head on each of its ``n_heads``
    leading logits arrays: ``(logits, ..., *rest) -> (token, finite, ...,
    *rest)``. The step functions keep their logits; the jitted programs
    return this."""
    heads = [x for logits in out[:n_heads] for x in greedy_head(logits)]
    return (*heads, *out[n_heads:])
