"""Spatial parallelism: halo exchange + spatially-sharded convolution.

Reference: ``reference:apex/contrib/bottleneck/bottleneck.py`` —
``SpatialBottleneck`` shards the image height across GPUs and exchanges
1-row halos over NCCL so the 3x3 convs see their neighbors' boundary rows
(the ``halo_exchange`` modes in ``bottleneck.py``; peer memory fast paths
in ``apex/contrib/csrc/peer_memory``).

TPU redesign: the halo transfer is a pair of ``ppermute`` neighbor shifts
(the ideal ICI pattern — exactly what the reference emulates with CUDA
peer-to-peer copies), and the boundary ranks substitute zero padding so
the sharded convolution reproduces a dense SAME conv bit-for-bit. Works
under AD: the transpose of a shift is the opposite shift, so halo
gradients flow back to their owners.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size as _axis_size

__all__ = ["halo_exchange", "spatial_conv2d"]


def halo_exchange(x: jnp.ndarray, axis_name: str, halo: int = 1,
                  spatial_axis: int = 1,
                  halo_top: Optional[int] = None,
                  halo_bottom: Optional[int] = None) -> jnp.ndarray:
    """Concatenate halo rows from the previous/next rank around this
    rank's shard (NHWC, height sharded by default). Boundary ranks get
    zeros — the SAME-padding rows of the equivalent dense conv.

    ``halo`` sets both sides; ``halo_top``/``halo_bottom`` override
    individually (strided SAME convs pad asymmetrically)."""
    ht = halo if halo_top is None else halo_top
    hb = halo if halo_bottom is None else halo_bottom
    cp = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % cp) for i in range(cp)]
    bwd = [(i, (i - 1) % cp) for i in range(cp)]

    parts = []
    if ht:
        bottom = jax.lax.slice_in_dim(
            x, x.shape[spatial_axis] - ht, x.shape[spatial_axis],
            axis=spatial_axis)
        from_prev = jax.lax.ppermute(bottom, axis_name, fwd)
        parts.append(jnp.where(rank == 0, jnp.zeros_like(from_prev),
                               from_prev))
    parts.append(x)
    if hb:
        top = jax.lax.slice_in_dim(x, 0, hb, axis=spatial_axis)
        from_next = jax.lax.ppermute(top, axis_name, bwd)
        parts.append(jnp.where(rank == cp - 1, jnp.zeros_like(from_next),
                               from_next))
    if len(parts) == 1:
        return x
    return jnp.concatenate(parts, axis=spatial_axis)


def spatial_conv2d(x: jnp.ndarray, w: jnp.ndarray, axis_name: str,
                   stride: int = 1) -> jnp.ndarray:
    """SAME 2D conv over an NHWC input whose HEIGHT is sharded on
    ``axis_name`` — each rank convolves its shard plus exchanged halos and
    the result equals the dense conv's corresponding height slice.

    Odd kernel sizes, ``kh > stride``, and ``stride`` must divide the
    local shard height (the reference's spatial bottleneck has the same
    alignment requirements for its strided convs). SAME with stride pads
    ``k - stride`` rows total when the size divides the stride, split
    low-first like XLA: top halo ``(k - stride) // 2``, bottom the rest.
    """
    kh, kw = w.shape[0], w.shape[1]
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError("spatial_conv2d requires odd kernel sizes")
    if x.shape[1] % stride:
        raise ValueError("stride must divide the local shard height")
    if kh <= stride:
        raise ValueError("kernel height must exceed stride")
    pad_h = kh - stride
    ht, hb = pad_h // 2, pad_h - pad_h // 2
    x = halo_exchange(x, axis_name, spatial_axis=1, halo_top=ht,
                      halo_bottom=hb)
    # height carries the SAME padding via halos/zeros; width pads locally
    # with the SAME formula (asymmetric under stride, low-first like XLA)
    W = x.shape[2]
    out_w = -(-W // stride)
    pad_w = max((out_w - 1) * stride + kw - W, 0)
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride),
        [(0, 0), (pad_w // 2, pad_w - pad_w // 2)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
