"""A routed (softmax top-k) model behind a cached prefix: the span program
pads its flat token buffer to a bucket, the padding tokens leave the span
kernel's attention undefined, and ``ops/moe.moe_mlp`` mixes every token of
a call in one product, so one non-finite row used to turn every logit into
NaN and every served id into 0 (PERF.md section 7 item 0, PR 27).
``forward_paged`` now zeroes the attention of tokens outside every span in
routed models: the greedy tokens behind a prefix hit are those of the same
requests served by fresh prefill."""

import numpy as np

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.models.transformer import init_params


def _serve(params, cfg, prefix_cache: bool):
    import jax

    ecfg = EngineConfig(
        backend="jax", model="t", temperature=0.0, max_tokens=5,
        max_batch_slots=2, page_size=16, num_pages=1, decode_block=5,
        prefix_cache=prefix_cache, host_kv=False, retry_attempts=1, seed=0)
    eng = JaxEngine(ecfg, cfg, params=params)
    pre = "the quarterly review covered the roadmap. "
    texts = []
    for wave in (["alpha"], ["beta two", "gamma"]):  # the second wave hits
        reqs = [GenerationRequest(prompt=pre + w, request_id=i,
                                  max_new_tokens=5, temperature=0.0)
                for i, w in enumerate(wave)]
        texts += [(r.text, r.completion_tokens)
                  for r in eng.generate_batch(reqs)]
    met = dict(eng._scheduler.metrics)
    eng.shutdown()
    del jax
    return texts, met


def test_routed_model_behind_a_cached_prefix_serves_the_fresh_prefills_tokens(
        monkeypatch):
    import jax

    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg = ModelConfig(name="t", vocab_size=512, dim=128, n_layers=2,
                      n_heads=1, n_kv_heads=1, head_dim=128, hidden_dim=64,
                      max_seq_len=128, n_experts=4, n_experts_per_token=2,
                      expert_capacity_factor=2.0, tie_embeddings=False,
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    fresh, met_fresh = _serve(params, cfg, prefix_cache=False)
    hit, met_hit = _serve(params, cfg, prefix_cache=True)
    assert met_fresh["prefix_hits"] == 0 and met_fresh["rpa_dispatches"] == 0
    assert met_hit["prefix_hits"] >= 2 and met_hit["rpa_dispatches"] >= 1
    assert all(n == 5 for _, n in fresh)
    assert hit == fresh
    assert np.all([len(t) > 0 or n > 0 for t, n in hit])
