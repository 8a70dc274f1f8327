"""Ulysses sequence parallelism: all-to-all context parallelism over ``cp``.

The second of the two long-context strategies SURVEY.md calls for ("ring
attention or all-to-all sequence/context parallelism" — the reference has
neither, §2.3). Where ring attention keeps the sequence sharded and rotates
K/V around the ring (cp × ppermute hops, O(S/cp) memory), Ulysses
re-shards: one all-to-all converts sequence-sharded [B, S/c, H, D] into
head-sharded [B, S, H/c, D], attention runs over the FULL sequence with
H/c local heads (so the un-sharded flash kernel applies directly), and a
second all-to-all restores sequence sharding.

Trade-offs vs ring (why both exist):
- Ulysses: 2 all-to-alls total (bandwidth-optimal on switched/ICI tori for
  moderate cp), full-sequence attention per device → head-count must be
  divisible by cp, memory O(S) per device for the attention inputs.
- Ring: cp neighbor hops, O(S/cp) memory, no head-divisibility constraint —
  the choice for extreme sequence lengths.

Same call shape as :func:`tony_tpu.parallel.ring_attention.ring_attention`.
"""

from __future__ import annotations

import functools
from typing import Sequence

from jax import lax
from jax.sharding import Mesh

from tony_tpu.parallel.ring_attention import _single_chunk
from tony_tpu.parallel.sharding import shard_attention


def ulysses_attention_local(q, k, v, *, axis_name: str = "cp",
                            causal: bool = True,
                            scale: float | None = None):
    """Per-shard Ulysses body — call inside ``shard_map`` with the sequence
    dim sharded over ``axis_name``.

    q: [B, S_local, H, D]; k/v: [B, S_local, H_kv, D] with H_kv | H —
    grouped-query K/V ride the all-to-all UNEXPANDED when H_kv divides
    the axis size: the kv-head dim splits over cp exactly like the query
    heads, and the contiguous split preserves group alignment (each cp
    rank's H/cp query heads are exactly (H_kv/cp)·(H/H_kv), so query
    head j still pairs with local kv head j // rep). The K/V payload —
    the strategy's whole inter-chip cost besides q/o — shrinks by
    H/H_kv. H and H_kv must both divide the axis size (the wrapper
    expands K/V first when H_kv cannot).
    Returns [B, S_local, H, D].
    """
    from tony_tpu.parallel.ring_attention import _flash_block, _flash_chunks

    b, s_loc, h, d = q.shape
    h_kv = k.shape[2]
    cp = lax.axis_size(axis_name)
    if h % cp:
        raise ValueError(f"n_heads={h} not divisible by {axis_name}={cp}")
    if h_kv % cp:
        raise ValueError(f"kv heads ({h_kv}) not divisible by "
                         f"{axis_name}={cp}; expand K/V first "
                         f"(ulysses_attention does this automatically)")
    if _flash_chunks() and _flash_block(s_loc * cp) is None:
        # Unlike ring chunks (S_local each), ulysses attends the FULL
        # gathered sequence per device — a silent dense fallback there
        # would materialize the O(S²) score tensor the strategy exists to
        # avoid. Fail with the remedy instead.
        raise ValueError(
            f"ulysses full sequence {s_loc * cp} does not tile any flash "
            f"block; pad the per-device full sequence to a multiple of "
            f"128 on TPU (8 in interpret mode)")
    if cp == 1:
        return _single_chunk(q, k, v, causal=causal, scale=scale)

    def seq_to_heads(x):
        # [B, S/c, H', D] → [B, S, H'/c, D]: split heads across the axis,
        # gather the full sequence (H' = H for q, H_kv for k/v).
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full sequence is local after the all-to-all; _single_chunk picks the
    # engine (flash pallas kernel on TPU with a tiling block, dense
    # otherwise) — one selection policy shared with the ring path; both
    # consume grouped K/V natively
    o = _single_chunk(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(o)


def ulysses_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                      scale: float | None = None,
                      batch_axes: Sequence[str] = ("dp", "fsdp"),
                      seq_axis: str = "cp", head_axis: str = "tp"):
    """Sequence-parallel attention over global [B, S, H, D] arrays — the
    all-to-all counterpart of :func:`ring_attention` (same call shape).
    Batch over dp/fsdp, sequence over cp, heads over tp; axes missing from
    ``mesh`` (or size 1) are dropped. With tp live, each tp shard runs
    Ulysses over its own head subset (local heads must still divide cp).

    GQA K/V (fewer heads than Q) ride the all-to-alls UNEXPANDED when the
    kv heads divide both the tp sharding and the cp split — the K/V
    payload shrinks by H/H_kv, the same discipline as the ring's
    unexpanded rotation. Otherwise (H_kv < tp·cp granularity) K/V expand
    to full width first — correctness over the payload saving."""
    cp = mesh.shape.get(seq_axis, 1)
    if cp > 1:
        fn = functools.partial(ulysses_attention_local, axis_name=seq_axis,
                               causal=causal, scale=scale)
    else:
        fn = functools.partial(_single_chunk, causal=causal, scale=scale)
    # the kv-head dim must survive the tp shard AND the local all-to-all
    # split: hk % (tp·cp) == 0 keeps every rank's local kv heads aligned
    # with its query-head groups
    return shard_attention(fn, q, k, v, mesh, batch_axes=batch_axes,
                           seq_axis=seq_axis, head_axis=head_axis,
                           kv_split=cp)
