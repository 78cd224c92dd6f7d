"""The paged decode kernel (``kernels/paged_decode.py``) in interpret mode
against the gather path it replaces on the chip: ``gather_paged_kv`` +
``decode_attention`` at HIGHEST precision.

Tolerance: f32 pools agree to 1e-5 (the online softmax only reorders the
sums).  bf16 pools agree to 2e-2: the kernel rounds the probabilities to
bf16 before the PV product, as a TPU's default-precision f32 product
does, and both round the output to bf16 (one ulp at 2-4 is 1.6e-2).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode import paged_decode_attention
from repro.models.attention import (decode_attention, gather_paged_kv,
                                    pages_read)

PS, MAXP, P = 16, 4, 12          # page size, table width, pages a layer
H, HKV = 32, 8
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
#: one slot of each kind: (table entries of its own, pos)
SLOTS = [
    ([3, 5, 0], 2 * PS - 1),         # frontier on a page's last row,
                                     # a page reserved past it
    ([1, 2, 7], 2 * PS),             # frontier on a page's first row
    ([4, 6], MAXP * PS + 5),         # finished, pos running past the table
    ([], 9),                         # reaped: entry 0 is the sentinel
    ([8, 9, 10], 2 * PS + 3),        # two slots sharing prefix pages
    ([8, 9, 11], 2 * PS + 7),
]


def operands(hd, flat, dtype, layers=1, layer=0, seed=0):
    """q, the [L·P, ...] pools, and the table offset to ``layer`` (the
    sentinel is L·P), as the carried tick hands them over."""
    n = layers * P
    shape = (n, PS, HKV * hd) if flat else (n, PS, HKV, hd)
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(k[0], shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(k[1], shape, jnp.float32).astype(dtype)
    q = jax.random.normal(k[2], (len(SLOTS), H, 1, hd)).astype(dtype)
    tbl = np.full((len(SLOTS), MAXP), n, np.int32)
    for b, (pages, _) in enumerate(SLOTS):
        tbl[b, :len(pages)] = np.asarray(pages) + layer * P
    pos = jnp.asarray([p for _, p in SLOTS], jnp.int32)
    return q, kp, vp, jnp.asarray(tbl), pos


def reference(q, kp, vp, tbl, pos, scale):
    hd = q.shape[-1]
    with jax.default_matmul_precision("highest"):
        return decode_attention(q, gather_paged_kv(kp, tbl, hd),
                                gather_paged_kv(vp, tbl, hd), pos,
                                scale=scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("scale", [None, 0.015625], ids=["hd", "mult"])
@pytest.mark.parametrize("layers,layer", [(1, 0), (3, 2)],
                         ids=["one-layer", "layer-offset"])
@pytest.mark.parametrize("hd,flat", [(128, False), (64, True)],
                         ids=["dense-heads", "hybrid-rows"])
def test_matches_gather_path(hd, flat, layers, layer, scale, dtype):
    q, kp, vp, tbl, pos = operands(hd, flat, dtype, layers, layer)
    scale = hd ** -0.5 if scale is None else scale
    got = np.asarray(paged_decode_attention(q, kp, vp, tbl, pos,
                                            scale=scale, interpret=True),
                     np.float32)
    want = np.asarray(reference(q, kp, vp, tbl, pos, scale), np.float32)
    reaped = np.asarray(tbl[:, 0] == layers * P)
    assert reaped.tolist() == [not pages for pages, _ in SLOTS]
    np.testing.assert_allclose(got[~reaped], want[~reaped],
                               atol=TOL[dtype], rtol=TOL[dtype])
    assert np.all(got[reaped] == 0.0)
    assert np.isfinite(got).all()


@pytest.mark.parametrize("hd,flat", [(128, False), (64, True)],
                         ids=["dense-heads", "hybrid-rows"])
def test_reads_only_live_pages(hd, flat):
    """Every page outside the slots' read sets is NaN: one such page read,
    even masked, would turn its slot's PV product NaN."""
    layers = 2
    q, kp, vp, tbl, pos = operands(hd, flat, jnp.float32, layers, 1)
    n = pages_read(tbl, pos, PS, layers * P)
    assert n.tolist() == [2, 3, MAXP, 0, 3, 3]
    read = np.zeros(layers * P, bool)
    for b in range(len(SLOTS)):
        read[np.minimum(np.asarray(tbl[b, :int(n[b])]), layers * P - 1)] = 1
    poison = jnp.asarray(~read)[:, None, None] if flat else \
        jnp.asarray(~read)[:, None, None, None]
    got = paged_decode_attention(q, jnp.where(poison, jnp.nan, kp),
                                 jnp.where(poison, jnp.nan, vp), tbl, pos,
                                 scale=hd ** -0.5, interpret=True)
    want = reference(q, kp, vp, tbl, pos, hd ** -0.5)
    live = np.asarray(n) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5, rtol=1e-5)
