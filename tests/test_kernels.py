"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret
mode executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[dt]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,Dh,causal,window",
    [
        (2, 128, 128, 4, 2, 64, True, 0),
        (1, 256, 256, 2, 1, 128, True, 0),
        (1, 192, 192, 4, 4, 32, True, 48),
        (2, 96, 96, 2, 2, 64, False, 0),
        (1, 130, 130, 2, 1, 64, True, 0),       # pad path
    ])
def test_flash_attention_sweep(B, Sq, Skv, H, Hkv, Dh, causal, window,
                               dtype):
    from repro.kernels.attention.ref import mha
    from repro.kernels.flash_attention.kernel import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = mha(q, k, v, causal=causal, window=window)
    err = np.abs(np.asarray(out, np.float32)
                 - np.asarray(ref, np.float32)).max()
    assert err < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,Dh,window",
                         [(2, 256, 4, 2, 64, 0),
                          (3, 200, 4, 1, 32, 64),
                          (1, 512, 8, 8, 128, 0)])
def test_decode_attention_sweep(B, S, H, Hkv, Dh, window, dtype):
    from repro.kernels.decode_attention.kernel import decode_attention
    from repro.kernels.decode_attention.ref import decode_attend
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, 1, H, Dh), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    lens = jax.random.randint(ks[3], (B,), window + 1, S + 1)
    out = decode_attention(q, kc, vc, lens, window=window, block_k=64,
                           interpret=True)
    ref = decode_attend(q, kc, vc, lens, window=window)
    err = np.abs(np.asarray(out, np.float32)
                 - np.asarray(ref, np.float32)).max()
    assert err < _tol(dtype), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,N,P,chunk",
                         [(2, 128, 2, 16, 16, 32),
                          (1, 200, 3, 32, 16, 64),     # pad path
                          (2, 256, 1, 8, 64, 128)])
def test_ssd_scan_sweep(B, S, H, N, P, chunk, dtype):
    from repro.kernels.ssd.ref import ssd
    from repro.kernels.ssd_scan.kernel import ssd_scan
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, S, H, N), dtype)
    k = jax.random.normal(ks[1], (B, S, H, N), dtype) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, P), dtype)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H))
                          ).astype(jnp.float32)
    y1, f1 = ssd_scan(q, k, v, la, chunk=chunk, interpret=True)
    y2, f2 = ssd(q, k, v, la, chunk=chunk)
    ey = np.abs(np.asarray(y1, np.float32)
                - np.asarray(y2, np.float32)).max()
    scale = np.abs(np.asarray(y2, np.float32)).max() + 1.0
    assert ey / scale < _tol(dtype), ey
    ef = np.abs(np.asarray(f1) - np.asarray(f2)).max()
    assert ef / (np.abs(np.asarray(f2)).max() + 1.0) < 5e-4


@pytest.mark.parametrize("B,D,C,n_keys",
                         [(64, 8, 128, 10), (256, 16, 512, 40),
                          (32, 8, 64, 1)])
def test_slate_update_sweep(B, D, C, n_keys):
    from repro.kernels.slate_update.kernel import slate_update as ker
    from repro.kernels.slate_update.ref import slate_update as ref
    rng = np.random.default_rng(B + D)
    keys = np.sort(rng.integers(0, n_keys, B)).astype(np.int32)
    deltas = rng.normal(size=(B, D)).astype(np.float32)
    run_last = np.concatenate([keys[1:] != keys[:-1], [True]])
    slots = np.where(run_last, (keys * 7 + 3) % C, -1).astype(np.int32)
    table = rng.normal(size=(C, D)).astype(np.float32)
    a, _ = ker(jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
               jnp.asarray(table), interpret=True)
    b = ref(jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
            jnp.asarray(table))
    err = np.abs(np.asarray(a) - np.asarray(b)).max()
    assert err < 1e-4, err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,D,offset", [(64, 64, False), (100, 128, True),
                                           (256, 32, False)])
def test_rmsnorm_sweep(rows, D, offset, dtype):
    from repro.kernels.rmsnorm.kernel import rmsnorm as ker
    from repro.kernels.rmsnorm.ref import rmsnorm as ref
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (2, rows, D), dtype)
    w = jax.random.normal(ks[1], (D,), jnp.float32)
    a = ker(x, w, scale_offset=offset, block_rows=32, interpret=True)
    b = ref(x, w, scale_offset=offset)
    err = np.abs(np.asarray(a, np.float32)
                 - np.asarray(b, np.float32)).max()
    assert err < _tol(dtype), err


def test_ssd_step_matches_scan_tail():
    """Decode-step recurrence agrees with the chunked scan."""
    from repro.kernels.ssd.ref import ssd, ssd_step
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    B, S, H, N, P = 1, 33, 2, 8, 8
    q = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, P))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    y_all, state_all = ssd(q, k, v, la, chunk=16)
    # replay step-by-step
    state = jnp.zeros((B, H, N, P))
    for t in range(S):
        state, y_t = ssd_step(state, q[:, t].swapaxes(1, 1),
                              k[:, t], v[:, t], la[:, t])
    assert np.allclose(np.asarray(state), np.asarray(state_all),
                       atol=1e-4)
    assert np.allclose(np.asarray(y_t), np.asarray(y_all[:, -1]),
                       atol=1e-4)
