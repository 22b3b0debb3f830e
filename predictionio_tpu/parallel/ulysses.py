"""Ulysses-style sequence parallelism: all-to-all head-scatter attention.

The second long-context strategy next to ``parallel.ring_attention`` (the
reference has no sequence models at all -- SURVEY.md section 5.7 -- so both
are new TPU-native capability). Where ring attention keeps queries resident
and rotates K/V blocks around the ICI ring (sp hops of [B, T/sp] blocks),
Ulysses re-shards ONCE per attention call: an all-to-all swaps the sharded
dimension from sequence to heads, every chip computes exact full-sequence
attention for its head group, and a second all-to-all swaps back.

Trade-off (why both exist): Ulysses moves 3 x [B, T, H/sp, D] per chip in
two fused all-to-alls -- cheaper than the ring's sp ppermute hops when the
head count divides nicely over the axis -- but caps the sequence axis at the
number of heads and materializes full-[T] K/V per chip. Ring has no head
constraint and never holds more than one remote block. Templates pick via
``seqParallel: "ring" | "ulysses"``.

All-to-alls ride ICI inside ``shard_map``; attention math reuses
``plain_attention`` so both strategies share one reference numerics path.
"""

from __future__ import annotations

import functools

import jax

from predictionio_tpu.parallel.mesh import seq_parallel_shard_map
from predictionio_tpu.parallel.ring_attention import plain_attention


def _ulysses_local(
    q, k, v, kv_mask, *, axis_name: str, causal: bool, sm_scale,
    use_flash: bool = False, interpret: bool = False,
):
    """Per-shard body. Shapes: q,k,v [B, Tl, H, D]; kv_mask [B, Tl].

    all_to_all #1: shard heads, gather sequence  -> [B, T, H/sp, D]
    local attention over the full sequence for H/sp heads (flash kernel
    when requested: the full-[T] score matrix is exactly what Ulysses
    would otherwise materialize per chip)
    all_to_all #2: shard sequence, gather heads  -> [B, Tl, H, D]
    """
    scatter = lambda x: jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    q_h, k_h, v_h = scatter(q), scatter(k), scatter(v)
    mask_full = jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    if use_flash:
        from predictionio_tpu.ops.flash_attention import flash_attention

        out = flash_attention(
            q_h, k_h, v_h, mask_full, causal=causal, sm_scale=sm_scale,
            interpret=interpret,
        )
    else:
        out = plain_attention(
            q_h, k_h, v_h, causal=causal, mask=mask_full, sm_scale=sm_scale
        )
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    axis_name: str = "seq",
    causal: bool = True,
    mask=None,
    sm_scale: float | None = None,
    use_flash: bool = False,
):
    """Attention with the sequence dim sharded over ``mesh[axis_name]``.

    Same contract as ``ring_attention``: global shapes q,k,v [B, T, H, D]
    with T divisible by the axis size, optional [B, T] key validity mask,
    batch sharding over a ``data`` axis when the mesh has one. Additional
    constraint: H must be divisible by the axis size (heads are the
    scattered dim).
    """
    import jax.numpy as jnp

    if mask is None:
        mask = jnp.ones(q.shape[:2], bool)
    axis_size = mesh.shape[axis_name]
    h = q.shape[2]
    if h % axis_size:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the '{axis_name}' "
            f"axis size ({axis_size}); use ring attention otherwise"
        )
    # flash-in-interpret (CPU tests) trips shard_map's vma checker on the
    # interpreter's internal index constants; this body never uses pcast,
    # so the check can be dropped exactly when that combination is active
    interpret = mesh.devices.flat[0].platform != "tpu"
    interpret_flash = use_flash and interpret
    fn = seq_parallel_shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, causal=causal,
            sm_scale=sm_scale, use_flash=use_flash, interpret=interpret,
        ),
        mesh,
        axis_name,
        check_vma=not interpret_flash,
    )
    return fn(q, k, v, mask)
