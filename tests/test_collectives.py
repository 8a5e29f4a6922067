import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.distributed import collectives as coll
from repro.distributed import optimizer as adamw


def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 5
    xhat, err = coll.compress_decompress(x)
    # per-block max / 127 bounds the elementwise error
    assert float(jnp.abs(err).max()) <= float(jnp.abs(x).max()) / 127 + 1e-6
    assert np.allclose(np.asarray(xhat + err), np.asarray(x), atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4096))
def test_quantize_any_length(n):
    x = jnp.linspace(-3, 7, n)
    xhat, err = coll.compress_decompress(x)
    assert xhat.shape == x.shape
    assert float(jnp.abs(err).max()) < 0.1


def test_error_feedback_unbiased_over_steps():
    """With error feedback, the *accumulated* compressed sum tracks the
    accumulated true sum (compression error does not accumulate)."""
    rng = jax.random.PRNGKey(1)
    err = jnp.zeros((257,))
    acc_hat = jnp.zeros((257,))
    acc_true = jnp.zeros((257,))
    for i in range(50):
        rng, k = jax.random.split(rng)
        g = jax.random.normal(k, (257,)) * 0.1 + 0.05
        acc_true = acc_true + g
        gc = g + err
        ghat, err = coll.compress_decompress(gc)
        acc_hat = acc_hat + ghat
    drift = float(jnp.abs(acc_hat - acc_true).max())
    # residual bounded by one step's quantization error, not 50 steps'
    assert drift < 0.02, drift


def test_compressed_psum_tree_single_device():
    """shard_map over a 1-device mesh: compressed psum == identity-ish."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(n_data=1, n_model=1)
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 8))}
    e = {"w": jnp.zeros((64, 8))}

    def f(gs, es):
        return coll.compressed_psum_tree(gs, es, "data")

    out, err = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False))(g, e)
    assert np.allclose(np.asarray(out["w"] + err["w"]),
                       np.asarray(g["w"]), atol=1e-6)


def test_adamw_minimizes_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
    for _ in range(200):
        grads = {"w": 2 * (params["w"] - target)}
        params, opt, m = adamw.update(params, grads, opt, cfg)
    assert np.allclose(np.asarray(params["w"]), np.asarray(target),
                       atol=0.05)
    assert int(opt.count) == 200


def test_grad_clip_caps_update():
    params = {"w": jnp.zeros(4)}
    opt = adamw.init(params)
    cfg = adamw.AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=1)
    grads = {"w": jnp.full((4,), 1e6)}
    _, _, metrics = adamw.update(params, grads, opt, cfg)
    assert float(metrics["grad_norm"]) > 1e6   # raw norm reported
