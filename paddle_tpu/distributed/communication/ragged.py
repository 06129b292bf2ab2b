"""Ragged (uneven-splits) all-to-all over a mesh axis (PR 10, VERDICT item 8).

XLA's ``all_to_all`` splits its operand evenly across the axis, so a true
``alltoall_single`` with per-rank row counts has been an API gap: MoE dispatch
padded every peer slice to the worst-case capacity bucket and shipped the
padding over the wire. This module closes the gap with the TPU-native
building blocks:

- ``exchange_counts``: a tiny dense [n, ...] count all-to-all so every rank
  learns how many real rows each peer is about to send it.
- ``ring_hop``: one ``ppermute`` shift of the ep ring (hop ``h`` sends to
  rank ``(i + h) % n``); n-1 hops realize the full personalized exchange
  while carrying only each destination's actual rows (padded to a static
  per-peer chunk so shapes stay SPMD-static — the pad is *per peer*, not
  the global capacity bucket, and in the MoE path each hop's chunk overlaps
  the grouped-GEMM on rows that already arrived).
- ``ragged_all_to_all``: the generic dest-major exchange built from the two,
  with a dense single-``all_to_all`` fallback carrying the identical chunk
  layout (bitwise-equal results, no per-hop overlap).

All transports move the same row values into the same slots, so downstream
consumers are bitwise-independent of the transport choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

from ...observability import trace as _obs


def exchange_counts(counts, axis_name, *, name="ragged_a2a.counts"):
    """All-to-all the per-destination count rows: ``counts[j]`` is what this
    rank is about to send rank ``j``; row ``j`` of the result is what rank
    ``j`` is about to send this rank. Shape [n, ...] -> [n, ...]."""
    counts = jnp.asarray(counts)
    n = _axis_size(axis_name)
    nbytes = int(counts.size * counts.dtype.itemsize)
    with _obs.comm_span(name, nbytes=nbytes, site="ragged_a2a.counts"):
        if n == 1:
            return counts
        return lax.all_to_all(counts, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


def ring_hop(x, axis_name, hop, *, name="ragged_a2a.hop"):
    """One hop of the ragged ring: every rank ``i`` sends ``x`` to rank
    ``(i + hop) % n`` (negative ``hop`` walks the reverse/return ring)."""
    n = _axis_size(axis_name)
    h = hop % n
    if h == 0:
        return x
    perm = [(i, (i + h) % n) for i in range(n)]
    nbytes = int(x.size * x.dtype.itemsize)
    with _obs.comm_span(name, nbytes=nbytes, site="ragged_a2a.hop"):
        return lax.ppermute(x, axis_name, perm)


def _pack_dest_major(rows, send_counts, n, peer_rows):
    """[R, ...] dest-sorted rows -> [n, peer_rows, ...] zero-padded chunks."""
    R = rows.shape[0]
    padded = jnp.concatenate(
        [rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)], axis=0)
    off = jnp.concatenate(
        [jnp.zeros((1,), send_counts.dtype), jnp.cumsum(send_counts)[:-1]])
    r = jnp.arange(peer_rows, dtype=send_counts.dtype)
    idx = jnp.where(r[None, :] < send_counts[:, None],
                    off[:, None] + r[None, :], R)
    return jnp.take(padded, idx, axis=0)


def ragged_all_to_all(rows, send_counts, axis_name, peer_rows, *,
                      impl="ring", name="ragged_a2a"):
    """Personalized exchange with uneven per-peer splits over ``axis_name``.

    ``rows`` is [R, ...] sorted by destination rank: the first
    ``send_counts[0]`` rows go to rank 0, the next ``send_counts[1]`` to
    rank 1, and so on (trailing rows beyond ``send_counts.sum()`` are
    ignored). ``peer_rows`` is the static per-peer chunk capacity — the most
    rows any rank may address to any single peer; each peer slice is
    zero-padded to it so SPMD shapes stay static, but only ``peer_rows``
    per hop crosses the wire instead of the global capacity bucket.

    Returns ``(out, recv_counts)``: ``out`` is [n * peer_rows, ...] where
    ``out[j * peer_rows : j * peer_rows + recv_counts[j]]`` are the rows
    rank ``j`` addressed to this rank (zero rows beyond each count), and
    ``recv_counts`` is [n]. ``impl="ring"`` walks n-1 ppermute hops;
    ``impl="dense"`` ships the identical chunk layout through one XLA
    all_to_all — both land bitwise-identical ``out``.
    """
    if impl not in ("ring", "dense"):
        raise ValueError(f"ragged_all_to_all: unknown impl {impl!r}")
    n = _axis_size(axis_name)
    send_counts = jnp.asarray(send_counts)
    send = _pack_dest_major(rows, send_counts, n, peer_rows)
    recv_counts = exchange_counts(send_counts, axis_name,
                                  name=f"{name}.counts")
    if n == 1:
        return send.reshape((peer_rows,) + rows.shape[1:]), recv_counts
    if impl == "dense":
        nbytes = int(send.size * send.dtype.itemsize)
        with _obs.comm_span(f"{name}.dense", nbytes=nbytes,
                            site="ragged_a2a.dense"):
            out = lax.all_to_all(send, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
    else:
        me = lax.axis_index(axis_name)
        out = jnp.zeros_like(send)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.take(send, me, axis=0), me, 0)
        for h in range(1, n):
            got = ring_hop(jnp.take(send, (me + h) % n, axis=0), axis_name,
                           h, name=f"{name}.hop")
            out = lax.dynamic_update_index_in_dim(out, got, (me - h) % n, 0)
    return out.reshape((n * peer_rows,) + rows.shape[1:]), recv_counts
