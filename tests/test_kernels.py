"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps,
plus hypothesis property tests on the chunked decay scan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.kd_softmax_kl import kd_loss_fwd
from repro.models import chunked_scan as cs

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------- kd loss
@pytest.mark.parametrize("T,V", [(128, 512), (256, 1024), (128, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kd_fwd_matches_ref(T, V, dtype):
    s = (jax.random.normal(KEY, (T, V)) * 3).astype(dtype)
    t = (jax.random.normal(jax.random.PRNGKey(1), (T, V)) * 3).astype(dtype)
    y = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    loss, _ = kd_loss_fwd(s, t, y, tau=2.0, alpha=0.5, interpret=True)
    lref = ref.kd_loss_ref(s, t, y, tau=2.0, alpha=0.5)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(loss), np.asarray(lref),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("tau,alpha", [(1.0, 0.0), (2.0, 0.5), (4.0, 1.0)])
def test_kd_fwd_tau_alpha(tau, alpha):
    T, V = 128, 512
    s = jax.random.normal(KEY, (T, V)) * 2
    t = jax.random.normal(jax.random.PRNGKey(1), (T, V)) * 2
    y = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    loss, _ = kd_loss_fwd(s, t, y, tau=tau, alpha=alpha, interpret=True)
    lref = ref.kd_loss_ref(s, t, y, tau=tau, alpha=alpha)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(lref),
                               rtol=1e-4, atol=1e-4)


def test_kd_padding_labels_masked():
    T, V = 128, 512
    s = jax.random.normal(KEY, (T, V))
    t = jax.random.normal(jax.random.PRNGKey(1), (T, V))
    y = jnp.full((T,), -1)
    loss, _ = kd_loss_fwd(s, t, y, interpret=True)
    assert float(jnp.abs(loss).sum()) == 0.0


def test_kd_custom_vjp_grad_matches_autodiff():
    T, V = 100, 700          # deliberately non-multiples -> exercises padding
    s = jax.random.normal(KEY, (T, V)) * 2
    t = jax.random.normal(jax.random.PRNGKey(1), (T, V)) * 2
    y = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)
    g = jax.grad(lambda s_: ops.kd_distillation_loss(s_, t, y, 2.0, 0.5, True))(s)
    gr = jax.grad(lambda s_: ref.kd_loss_ref(s_, t, y, tau=2.0, alpha=0.5).mean())(s)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5,
                               atol=1e-6)


def test_kd_batched_shapes():
    B, T, V = 2, 64, 512
    s = jax.random.normal(KEY, (B, T, V))
    t = jax.random.normal(jax.random.PRNGKey(1), (B, T, V))
    y = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    loss = ops.kd_distillation_loss(s, t, y, 2.0, 0.5, True)
    lref = ref.kd_loss_ref(s.reshape(-1, V), t.reshape(-1, V),
                           y.reshape(-1)).mean()
    np.testing.assert_allclose(float(loss), float(lref), rtol=1e-5)


def test_interpret_default_follows_backend(monkeypatch):
    """CPU interprets, TPU compiles, anything else raises rather than
    quietly interpreting on a device it was not built for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret_default() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret_default()


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,H,KVH,T,S,hd", [
    (1, 4, 4, 64, 64, 32),
    (2, 8, 2, 128, 128, 64),
    (1, 4, 2, 100, 100, 32),        # padding path
    (2, 4, 4, 64, 256, 64),         # cross-length (decode-ish, right-aligned)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KVH, T, S, hd, dtype):
    q = jax.random.normal(KEY, (B, T, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, hd)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, hd)).astype(dtype)
    o = ops.flash_attention(q, k, v, causal=True, interpret=True)
    oref = ref.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1), causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(jnp.moveaxis(oref, 1, 2), np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_windowed():
    B, H, T, hd, W = 1, 2, 128, 32, 32
    q = jax.random.normal(KEY, (B, T, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, hd))
    o = ops.flash_attention(q, k, v, causal=True, window=W, interpret=True)
    oref = ref.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                   jnp.moveaxis(v, 2, 1), causal=True, window=W)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(jnp.moveaxis(oref, 1, 2)),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- fused merge
@pytest.mark.parametrize("N,D", [(3, 512), (8, 1024), (5, 100), (1, 7),
                                 (13, 513)])   # non-multiples hit padding
@pytest.mark.parametrize("decay", [0.0, 0.5, 1.5])
def test_fused_merge_matches_ref(N, D, decay):
    x = jax.random.normal(KEY, (N, D)) * 2
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (N,))) + 0.1
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (N,))).astype(
        jnp.int32).astype(jnp.float32) * 2
    out = ops.fused_merge(x, w, s, decay=decay, interpret=True)
    oref = ref.fused_merge_ref(x, w, s, decay=decay)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oref),
                               rtol=1e-5, atol=1e-5)


def test_fused_merge_no_staleness_is_weighted_mean():
    N, D = 4, 300
    x = jax.random.normal(KEY, (N, D))
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    out = ops.fused_merge(x, w, interpret=True)
    expect = (x * (w / w.sum())[:, None]).sum(0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # decay on all-zero staleness changes nothing
    out_d = ops.fused_merge(x, w, jnp.zeros(N), decay=0.7, interpret=True)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out),
                               rtol=1e-6, atol=1e-6)


def test_fused_merge_nd_leaf_and_dtype():
    """(N, ...) leaves of any rank/dtype flatten through the kernel and come
    back float32 in the leaf shape (callers cast back)."""
    x = (jax.random.normal(KEY, (5, 3, 4, 7)) * 3).astype(jnp.bfloat16)
    w = jnp.ones(5)
    out = ops.fused_merge(x, w, interpret=True)
    assert out.shape == (3, 4, 7) and out.dtype == jnp.float32
    oref = ref.fused_merge_ref(x.reshape(5, -1).astype(jnp.float32), w)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), np.asarray(oref),
                               rtol=2e-2, atol=2e-2)


def test_fused_merge_staleness_downweights():
    """A very stale contributor loses influence monotonically in decay."""
    x = jnp.stack([jnp.zeros(64), jnp.ones(64)])
    w = jnp.ones(2)
    s = jnp.asarray([0.0, 5.0])
    prev = 1.0
    for decay in (0.0, 0.5, 1.0, 2.0):
        got = float(ops.fused_merge(x, w, s, decay=decay,
                                    interpret=True).mean())
        assert got <= prev + 1e-7
        prev = got
    assert prev < 0.1     # decay=2: (1+5)^-2 ~ 0.028 vs 1.0


# ------------------------------------------------------------------ kmeans
@pytest.mark.parametrize("N,F,K", [(64, 8, 3), (97, 12, 5), (256, 24, 8)])
def test_kmeans_assign_matches_ref(N, F, K):
    x = jax.random.normal(KEY, (N, F))
    c = jax.random.normal(jax.random.PRNGKey(1), (K, F))
    a, d = ops.kmeans_assign(x, c, interpret=True)
    ar, dr = ref.kmeans_assign_ref(x, c)
    assert bool(jnp.all(a == ar))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-4,
                               atol=1e-4)


# ------------------------------------------------------ chunked decay scan
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([8, 16, 32]),
       st.sampled_from([17, 32, 48]), st.booleans())
def test_chunked_scan_matches_sequential(seed, chunk, T, bonus):
    key = jax.random.PRNGKey(seed)
    B, H, dk, dv = 1, 2, 4, 6
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, T, dk))
    k = jax.random.normal(ks[1], (B, H, T, dk))
    v = jax.random.normal(ks[2], (B, H, T, dv if not bonus else dk))
    la = -jnp.abs(jax.random.normal(ks[3], (B, H, T, dk))) * 0.7
    u = jnp.abs(jax.random.normal(ks[4], (H, dk))) if bonus else None
    y1, s1 = cs.chunked_decay_scan(q, k, v, la, u=u, chunk=chunk,
                                   bonus_mode=bonus)
    y2, s2 = cs.reference_scan(q, k, v, la, u=u, bonus_mode=bonus)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4,
                               atol=2e-4)


def test_chunked_scan_init_state_chaining():
    """Processing [0:T/2] then [T/2:T] with carried state == full scan."""
    B, H, T, dk, dv = 1, 2, 32, 4, 4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, T, dk))
    k = jax.random.normal(ks[1], (B, H, T, dk))
    v = jax.random.normal(ks[2], (B, H, T, dv))
    la = -jnp.abs(jax.random.normal(ks[3], (B, H, T, 1))) * 0.5
    y_full, s_full = cs.chunked_decay_scan(q, k, v, la, chunk=8)
    h = T // 2
    y1, s1 = cs.chunked_decay_scan(q[:, :, :h], k[:, :, :h], v[:, :, :h],
                                   la[:, :, :h], chunk=8)
    y2, s2 = cs.chunked_decay_scan(q[:, :, h:], k[:, :, h:], v[:, :, h:],
                                   la[:, :, h:], init_state=s1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=2)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=2e-4,
                               atol=2e-4)


def test_log_decay_clamp_applied_consistently():
    """Very strong decays: chunked and sequential must still agree (both
    clamp at LOG_DECAY_FLOOR)."""
    B, H, T, dk = 1, 1, 16, 4
    q = jnp.ones((B, H, T, dk))
    k = jnp.ones((B, H, T, dk))
    v = jnp.ones((B, H, T, dk))
    la = jnp.full((B, H, T, dk), -50.0)
    y1, _ = cs.chunked_decay_scan(q, k, v, la, chunk=8)
    y2, _ = cs.reference_scan(q, k, v, la)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(y1)))
