"""Kernel-vs-oracle sweeps (interpret mode on CPU; same code targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitops, frdc
from repro.kernels import bmm_kernel, bspmm_kernel, pack_kernel, ref

jax.config.update("jax_platform_name", "cpu")


def _rand_packed(rng, rows, nbits):
    raw = rng.choice([-1.0, 1.0], size=(rows, nbits))
    return bitops.pack_bits(raw > 0), raw


@pytest.mark.parametrize("m,n,k", [
    (8, 32, 32), (16, 64, 96), (3, 33, 65), (130, 40, 256), (1, 1, 7),
])
def test_bmm_xnor_kernel_matches_ref(m, n, k):
    rng = np.random.default_rng(m * 1000 + n * 10 + k)
    ap, _ = _rand_packed(rng, m, k)
    bp, _ = _rand_packed(rng, n, k)
    got = bmm_kernel.bmm_xnor(ap, bp, k, block_m=32, block_n=32,
                              interpret=True)
    want = ref.bmm_xnor_ref(ap, bp, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,n,k", [(8, 64, 32), (5, 96, 128), (9, 40, 64)])
def test_bmm_xnor_binarize_fused(m, n, k):
    rng = np.random.default_rng(m + n + k)
    ap, _ = _rand_packed(rng, m, k)
    bp, _ = _rand_packed(rng, n, k)
    got = bmm_kernel.bmm_xnor(ap, bp, k, binarize=True, block_m=32,
                              block_n=32, interpret=True)
    want = ref.bmm_xnor_bin_ref(ap, bp, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,f", [(8, 32), (3, 100), (65, 256), (1, 31)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_binarize_pack_kernel(m, f, dtype):
    rng = np.random.default_rng(m * f)
    x = jnp.asarray(rng.standard_normal((m, f)), dtype)
    got = pack_kernel.binarize_pack(x, block_m=32, block_f=64,
                                    interpret=True)
    want = ref.binarize_pack_ref(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _graph(rng, n, density):
    return (rng.random((n, n)) < density).astype(np.float32)


@pytest.mark.parametrize("n,f,density", [
    (16, 32, 0.3), (40, 64, 0.1), (33, 96, 0.25), (64, 32, 0.05),
])
def test_bspmm_bits_kernel_binarized(n, f, density):
    rng = np.random.default_rng(n * f)
    adj = frdc.from_dense(_graph(rng, n, density))
    act = rng.choice([-1.0, 1.0], size=(n, f))
    xp = bitops.pack_bits(act > 0)
    got = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=True)
    want = ref.bspmm_bits_ref(adj, xp, f, binarize=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["s2_and_andnot", "s3_two_popc"])
def test_bspmm_bits_kernel_counts(mode):
    rng = np.random.default_rng(7)
    n, f = 24, 64
    adj = frdc.from_dense(_graph(rng, n, 0.2))
    act = rng.choice([-1.0, 1.0], size=(n, f))
    xp = bitops.pack_bits(act > 0)
    got = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=False,
                                  trinary_mode=mode)
    want = ref.bspmm_bits_ref(adj, xp, f, binarize=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,f,density", [(16, 32, 0.3), (41, 128, 0.15)])
def test_bspmm_fp_kernel(n, f, density):
    rng = np.random.default_rng(n + f)
    adj = frdc.from_dense(_graph(rng, n, density))
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    got = bspmm_kernel.bspmm_fp(adj, x)
    want = ref.bspmm_fp_ref(adj, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bspmm_empty_rows_prefill():
    """Rows with no edges: counts path gives 0, binarized path gives sign(0)=+1."""
    n, f = 16, 32
    a = np.zeros((n, n), np.float32)
    a[0, 3] = 1.0   # only tile-row 0 has a group
    adj = frdc.from_dense(a)
    rng = np.random.default_rng(0)
    act = rng.choice([-1.0, 1.0], size=(n, f))
    xp = bitops.pack_bits(act > 0)
    counts = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=False)
    np.testing.assert_array_equal(np.asarray(counts[4:]), 0)
    bits = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=True)
    np.testing.assert_array_equal(np.asarray(bits[4:]), 0xFFFFFFFF)


def test_bspmm_dma_start_wait_descriptors_pair():
    """Step-② DMA regression: every started HBM->VMEM gather must be waited
    on with the SAME descriptor (source slice included). Both kernels build
    start and wait through the shared ``_gather_copy`` helper; record its
    calls per kernel trace and check the wait half mirrors the start halves
    slot for slot — a wait reconstructed from a different source slice
    (e.g. a constant ``x_hbm[0:TILE]``) would bypass the helper and break
    the pattern."""
    calls = []
    real = bspmm_kernel._gather_copy

    def spy(w, mslot, g, t, slot, fb):
        calls.append(t)
        return real(w, mslot, g, t, slot, fb)

    rng = np.random.default_rng(5)
    adj = frdc.from_dense(_graph(rng, 24, 0.2))
    x = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    act = rng.choice([-1.0, 1.0], size=(24, 32))
    xp = bitops.pack_bits(act > 0)
    bspmm_kernel._gather_copy = spy
    try:
        got_fp = bspmm_kernel.bspmm_fp(adj, x)
        got_bits = bspmm_kernel.bspmm_bits(adj, xp, 32, binarize=False)
    finally:
        bspmm_kernel._gather_copy = real
    # each kernel-body trace of the double-buffered walk issues the first
    # group's GROUP starts, the next group's GROUP starts, then the current
    # group's GROUP waits — always over the same slot sequence
    walk = 3 * frdc.GROUP
    assert calls and len(calls) % walk == 0
    for i in range(0, len(calls), walk):
        window = calls[i:i + walk]
        for j in range(0, walk, frdc.GROUP):
            assert window[j:j + frdc.GROUP] == list(range(frdc.GROUP))
    # and the kernels still agree with the oracles through the spy
    np.testing.assert_allclose(np.asarray(got_fp),
                               np.asarray(ref.bspmm_fp_ref(adj, x)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got_bits),
        np.asarray(ref.bspmm_bits_ref(adj, xp, 32, binarize=False)))


def test_bspmm_bits_block_validates_real_feature_width():
    """``bspmm_bits`` used to validate ``block_shape`` against the padded
    word width ``wf * WORD``: a block equal to the caller's REAL (narrower)
    ``n_feat`` bounced off the word-alignment check even though the packed
    kernel's word-native storage covers it exactly. Validation now sees the
    actual feature width."""
    n, f = 16, 24                    # wf = 1 word; wf * WORD = 32 > f
    rng = np.random.default_rng(9)
    adj = frdc.from_dense(_graph(rng, n, 0.25))
    act = rng.choice([-1.0, 1.0], size=(n, f))
    xp = bitops.pack_bits(act > 0)
    want = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=False)
    # a block matching the real feature width is legal (used to raise) and
    # changes nothing
    got = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=False,
                                  block_shape=(4, f))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_bin = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=True,
                                      block_shape=(4, f))
    np.testing.assert_array_equal(
        np.asarray(got_bin),
        np.asarray(bspmm_kernel.bspmm_bits(adj, xp, f, binarize=True)))
    # genuinely unsupported widths still fail loudly
    with pytest.raises(ValueError):
        bspmm_kernel.bspmm_bits(adj, xp, f, binarize=False,
                                block_shape=(4, 48))
    assert bspmm_kernel._resolve_block((4, 24), 24, True) == 24
    with pytest.raises(ValueError):
        bspmm_kernel._resolve_block((4, 24), 32, True)


def test_bspmm_kernel_bucket_padded_frdc():
    """pad_frdc bucket padding appends all-zero groups mapped to tile-row 0
    WITHOUT a first-of-row reset. The kernel's flush schedule must neither
    let a pad group close row 0 with a stale accumulator nor hide row 0's
    real last group behind the pads (both bugs existed): padded and
    unpadded results must agree, including the row-0-only corner."""
    # corner: the ONLY real group is in tile-row 0, pads follow in row 0
    m = frdc.pad_frdc(frdc.from_coo([0], [0], 1, 1), 64, n_groups=16)
    x = jnp.ones((64, 5), jnp.float32)
    got = np.asarray(bspmm_kernel.bspmm_fp(m, x))[:1]
    np.testing.assert_array_equal(got, [[1.0] * 5])

    rng = np.random.default_rng(3)
    a = (rng.random((30, 30)) < 0.2).astype(np.float32)
    adj = frdc.from_dense(a)
    xf = jnp.asarray(rng.standard_normal((30, 32)), jnp.float32)
    want_fp = np.asarray(bspmm_kernel.bspmm_fp(adj, xf))[:30]
    padded = frdc.pad_frdc(adj, 64, n_groups=adj.n_groups + 7)
    xf_pad = jnp.zeros((64, 32)).at[:30].set(xf)
    got_fp = np.asarray(bspmm_kernel.bspmm_fp(padded, xf_pad))[:30]
    np.testing.assert_allclose(got_fp, want_fp, rtol=1e-5, atol=1e-5)

    act = rng.choice([-1.0, 1.0], size=(30, 32))
    xp = bitops.pack_bits(act > 0)
    want_c = np.asarray(bspmm_kernel.bspmm_bits(adj, xp, 32,
                                                binarize=False))[:30]
    xp_pad = jnp.zeros((64, 1), jnp.uint32).at[:30].set(xp)
    got_c = np.asarray(bspmm_kernel.bspmm_bits(padded, xp_pad, 32,
                                               binarize=False))[:30]
    np.testing.assert_array_equal(got_c, want_c)
