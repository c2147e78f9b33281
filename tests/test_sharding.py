"""Multi-chip sharding tests on the virtual 8-device CPU mesh (SURVEY.md §4:
emulate TP/DP without TPUs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lmrs_tpu.config import MeshConfig, ModelConfig
from lmrs_tpu.models.transformer import forward, init_params
from lmrs_tpu.parallel.mesh import build_mesh
from lmrs_tpu.parallel.sharding import param_shardings, shard_params

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def cfg8():
    return ModelConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                       hidden_dim=64, max_seq_len=128, dtype="float32")


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, tp=2, sp=2, pp=1))
    assert mesh.shape == {"dp": 2, "tp": 2, "sp": 2, "ep": 1, "pp": 1}


def test_mesh_too_big_raises():
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=16, tp=2))


def test_tp_sharded_forward_matches_single_device():
    """TP=2 sharded forward must be numerically identical (up to f32 noise)
    to the unsharded forward — XLA inserts the collectives."""
    cfg = cfg8()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))

    ref_logits, _ = forward(params, cfg, tokens, pos)

    mesh = build_mesh(MeshConfig(dp=2, tp=2, sp=1, pp=1))
    sharded = shard_params(params, mesh, cfg.tie_embeddings)

    @jax.jit
    def run(p, t, pos):
        logits, _ = forward(p, cfg, t, pos)
        return logits

    out = run(sharded, tokens, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-5)


def test_param_sharding_layout():
    """Head/vocab/ffn axes actually land on the tp mesh axis."""
    cfg = cfg8()
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(dp=1, tp=2, sp=1, pp=1), jax.devices()[:2])
    sharded = shard_params(params, mesh, cfg.tie_embeddings)
    wq = sharded["layers"]["attn"]["wq"]
    # wq [L, D, H, hd] sharded on H over tp=2: per-device shard has H/2
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[2] == cfg.n_heads // 2
    emb = sharded["embed"]["weight"]
    assert emb.sharding.shard_shape(emb.shape)[0] == cfg.vocab_size // 2


def test_training_step_on_mesh():
    """Full sharded train step (the dryrun_multichip path) runs and reduces
    loss over a few steps on memorizable data."""
    import optax

    from lmrs_tpu.training.train import make_train_step

    cfg = cfg8()
    mesh = build_mesh(MeshConfig(dp=2, tp=2, sp=2, pp=1))
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), mesh,
                          cfg.tie_embeddings)
    opt = optax.adam(5e-3)
    opt_state = opt.init(params)
    step = make_train_step(cfg, opt, mesh, seq_sharded=True)
    tokens = jnp.asarray(
        np.tile(np.arange(32, dtype=np.int32)[None], (4, 2)).reshape(4, 64) % 64
    )
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_dryrun_multichip_entrypoint():
    import importlib.util, pathlib

    spec = importlib.util.spec_from_file_location(
        "graft_entry", pathlib.Path(__file__).parent.parent / "__graft_entry__.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


def test_seq_sharded_ring_loss_matches_unsharded():
    """sp>1 training loss (ring attention path) must equal the unsharded
    causal-LM loss to f32 tolerance."""
    import optax

    from lmrs_tpu.training.train import causal_lm_loss, make_train_step

    cfg = cfg8()
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 64)
    want = float(causal_lm_loss(params, cfg, tokens))

    mesh = build_mesh(MeshConfig(dp=2, tp=1, sp=4, pp=1))
    sharded = shard_params(params, mesh, cfg.tie_embeddings)
    opt = optax.sgd(0.0)  # zero LR: step returns the pristine loss
    step = make_train_step(cfg, opt, mesh, seq_sharded=True)
    _, _, loss = step(sharded, opt.init(sharded), tokens)
    np.testing.assert_allclose(float(loss), want, rtol=1e-4)


def test_remat_grads_match_non_remat():
    """jax.checkpoint per layer must not change loss or grads (only the
    backward-pass memory/FLOP schedule)."""
    from lmrs_tpu.training.train import causal_lm_loss

    cfg = cfg8()
    params = init_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 32), 0, 64)
    l_ref, g_ref = jax.value_and_grad(causal_lm_loss)(params, cfg, tokens)
    l_rm, g_rm = jax.value_and_grad(causal_lm_loss)(params, cfg, tokens,
                                                    remat=True)
    np.testing.assert_allclose(float(l_rm), float(l_ref), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_rm)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


def test_remat_train_step_on_mesh():
    import optax

    from lmrs_tpu.training.train import make_train_step

    cfg = cfg8()
    mesh = build_mesh(MeshConfig(dp=2, tp=2, sp=1, pp=1), jax.devices()[:4])
    params = shard_params(init_params(cfg, jax.random.PRNGKey(7)), mesh,
                          cfg.tie_embeddings)
    opt = optax.adam(1e-3)
    step = make_train_step(cfg, opt, mesh, remat=True)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (4, 32), dtype=np.int32))
    _, _, loss = step(params, opt.init(params), tokens)
    assert np.isfinite(float(loss))
