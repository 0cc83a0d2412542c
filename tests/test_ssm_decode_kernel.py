"""The Mamba2 decode state-update kernel (``kernels/ssm_decode``), in
interpret mode, against the jnp recurrence (``kernels/ref.ssm_decode_ref``):
the layer it is given advances by one step, every other layer of the
stacked state is left bit for bit, and the read-out matches."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.kernels import ops, ref, ssm_decode
from repro.models import ssm as ssm_mod

_smoke = cfgs.get_smoke_config("mamba2-2.7b")
_, _H, _N = ssm_mod.ssm_dims(_smoke)
SHAPES = {
    # (layers, requests, heads, head dim, state): the smoke widths, and one
    # shape of whole 8 x 8 blocks, two along each grid axis
    "smoke": (_smoke.n_layers, 2, _H, _smoke.ssm_head_dim, _N),
    "blocks": (3, 16, 16, 64, 128),
}


def _inputs(shape, seed=0):
    n_layers, b, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    state = jax.random.normal(ks[0], shape, jnp.float32)
    da = jnp.exp(-jax.random.uniform(ks[1], (b, h), jnp.float32))
    dtx = jax.random.normal(ks[2], (b, h, p), jnp.float32)
    b_mat = jax.random.normal(ks[3], (b, n), jnp.float32)
    c_mat = jax.random.normal(ks[4], (b, n), jnp.float32)
    return state, da, dtx, b_mat, c_mat


@pytest.mark.parametrize("name", sorted(SHAPES))
@pytest.mark.parametrize("where", ["first", "last"])
def test_kernel_advances_one_layer_and_leaves_the_rest(name, where):
    shape = SHAPES[name]
    layer = 0 if where == "first" else shape[0] - 1
    state, da, dtx, b_mat, c_mat = _inputs(shape)
    before = np.asarray(state)
    new, y = ops.ssm_decode_update(state, jnp.int32(layer), da, dtx, b_mat,
                                   c_mat, interpret=True)
    want, want_y = ref.ssm_decode_ref(state[layer], da, dtx, b_mat, c_mat)
    new = np.asarray(new)
    assert new.shape == shape and y.shape == shape[1:4]
    np.testing.assert_allclose(new[layer], np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.delete(new, layer, 0),
                                  np.delete(before, layer, 0))
    # y sums N products; only the order of that sum may differ
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5,
                               atol=1e-5 * np.sqrt(shape[-1]))


@pytest.mark.parametrize("where", ["first", "last"])
def test_state_off_the_lanes_is_updated_by_xla(where):
    """Compiled for a state whose N does not fill 128 lanes, the update is
    XLA's, on the layer's slice: the same step, the other layers as they
    were (it needs no TPU, so it runs here as it runs there)."""
    shape = SHAPES["smoke"]
    assert shape[-1] % ssm_decode.LANES
    layer = 0 if where == "first" else shape[0] - 1
    state, da, dtx, b_mat, c_mat = _inputs(shape, seed=2)
    new, y = jax.jit(functools.partial(ssm_decode.ssm_decode_update,
                                       interpret=False))(
        state, jnp.int32(layer), da, dtx, b_mat, c_mat)
    want, want_y = ref.ssm_decode_ref(state[layer], da, dtx, b_mat, c_mat)
    new = np.asarray(new)
    np.testing.assert_allclose(new[layer], np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.delete(new, layer, 0),
                                  np.delete(np.asarray(state), layer, 0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5,
                               atol=1e-5 * np.sqrt(shape[-1]))


def test_kernel_takes_the_layer_index_from_the_carry():
    """Inside a scan over the layer index, as ``decode_step`` runs it, each
    layer is advanced with its own inputs."""
    shape = SHAPES["blocks"]
    state, da, dtx, b_mat, c_mat = _inputs(shape, seed=1)
    scale = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)

    def body(st, i):
        st, y = ops.ssm_decode_update(st, i, da, dtx * scale[i], b_mat,
                                      c_mat, interpret=True)
        return st, y

    new, ys = jax.lax.scan(body, state, jnp.arange(shape[0]))
    for i in range(shape[0]):
        want, want_y = ref.ssm_decode_ref(state[i], da, dtx * scale[i],
                                          b_mat, c_mat)
        np.testing.assert_allclose(np.asarray(new[i]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(want_y),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape,want", [
    ((128, 80, 64, 128), (8, 8)),      # mamba2-2.7b at the benchmark's batch
    ((128, 80, 4, 128), (64, 8)),      # P pads to eights in VMEM
    ((2, 8, 16, 16), (2, 8)),          # the smoke widths: one block
    ((6, 4, 64, 128), (6, 4)),         # heads not in eights: all of them
    ((100, 80, 64, 128), (5, 8)),      # a batch not in eights
])
def test_blocks_follow_the_shapes(shape, want):
    b, h, p, n = shape
    bb, bh = ssm_decode.blocks(b, h, p, n)
    assert (bb, bh) == want
    assert b % bb == 0 and h % bh == 0
    assert bb * bh * ssm_decode.vmem_bytes(p, n) <= ssm_decode.BLOCK_BYTES
