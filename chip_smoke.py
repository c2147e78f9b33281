"""chip_smoke.py — the first thing to run on hardware.

Drives the system's main path once on a TPU, through the entry points a user
would call, at Llama-3-8B width (``bench-8b``: published widths, full depth,
int8 weights + int8 KV so one 16 GB chip holds it; random weights from the
seed), and fails unless every phase passes:

1. native runtime rebuilt from ``native/src`` (no stale ``native/build``);
2. **server** — ``python -m lmrs_tpu.serving.cli --backend jax ...`` as a
   CHILD (the parent has not touched JAX: a chip belongs to one process),
   concurrent ``POST /v1/chat/completions`` incl. one ``"stream": true``,
   ``/healthz``, ``/metrics`` (decode tokens, ragged-span and row-group
   dispatch counters), clean SIGTERM;
3. **kernel parity** — each Pallas kernel vs its XLA reference (flash,
   packed flash, fused decode at row_group 1 and 4, multi-token verify,
   ragged spans, bf16 and int8 pools), no per-check ``except``;
4. **map-reduce job** — ``lmrs_tpu.cli.main([...])`` in process on a seeded
   synthetic transcript, ``--report`` checked (0 failed requests, map and
   reduce stages, kernel gates still armed after the run);
5. **kernels vs XLA at model width** — greedy tokens of a few prompts from
   the kernel path vs a second scheduler on the SAME device-resident params
   whose ``_use_ragged`` / ``_use_flash`` gates this script sets to the XLA
   path before its first dispatch.

``--chips 4`` runs ONLY the four-chip path: ``llama3-8b`` in bf16 on
``MeshConfig(tp=4)`` (shard_map-wrapped kernels vs the XLA path on the same
mesh, shard placement and collectives asserted) and a dp=2 x tp=2
``ReplicatedEngine``.

Last stdout line on success, exactly:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Without a TPU the script exits non-zero and never prints ``"ok": true``.
The phases are functions of a model preset and sizes so a scratch script can
rehearse their control flow on the CPU (``LMRS_FORCE_KERNELS=interpret``);
``main()`` takes no size option.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"
SEED = 0

_WORDS = ("the quarterly review covered the inference engine roadmap kernel "
          "design latency targets hiring plan budget allocation serving tier "
          "page pool prefix cache scheduler admission decode block rollout "
          "incident follow-up owners deadline risks mitigation").split()


def log(msg: str) -> None:
    print(msg, flush=True)


def _text(rng, n_bytes: int) -> str:
    out, size = [], 0
    while size < n_bytes:
        w = rng.choice(_WORDS)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def synth_transcript(seed: int, n_segments: int) -> dict:
    """Seeded diarized transcript (README schema) — generated here so the
    smoke's input never depends on a path outside the checkout."""
    import random

    rng = random.Random(seed)
    segs, t = [], 0.0
    for i in range(n_segments):
        dur = 4.0 + rng.random() * 8.0
        segs.append({"start": round(t, 2), "end": round(t + dur, 2),
                     "text": _text(rng, 90 + rng.randrange(60)).capitalize()
                     + ".",
                     "speaker": f"SPEAKER_{(i // 3) % 3:02d}"})
        t += dur + rng.random()
    return {"segments": segs}


# ------------------------------------------------------------ device probe


def probe_device() -> dict:
    """Ask JAX for the default device in a short-lived CHILD, so the parent
    stays off the chip until the server child has come and gone."""
    code = ("import json, jax; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=str(ROOT))
    if r.returncode != 0:
        raise RuntimeError("device probe failed (no accelerator?):\n"
                           + r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def require_tpu(dev: dict, chips: int) -> None:
    if dev["platform"] != "tpu":
        raise RuntimeError(f"no TPU found: JAX reports platform "
                           f"{dev['platform']!r} ({dev['kind']}); "
                           "chip_smoke.py runs on a TPU only")
    if dev["count"] != chips:
        raise RuntimeError(f"expected {chips} chip(s), JAX reports "
                           f"{dev['count']}")


# ----------------------------------------------------------- native runtime


def rebuild_native() -> str:
    """Rebuild the native runtime from ``native/src`` (``native/build`` is
    git-ignored, so a stale .so can ride along in a copied tree) and say
    which page allocator the engine will get."""
    from lmrs_tpu.runtime import native

    shutil.rmtree(native._LIB.parent, ignore_errors=True)
    have_cxx = shutil.which(os.environ.get("CXX", "g++")) is not None
    ok = native.native_available()
    if have_cxx and not ok:
        raise RuntimeError("g++ is present but the native runtime build "
                           "failed (see the lmrs.native warning above)")
    status = ("native (C++ page allocator, built from native/src)" if ok
              else "python (no g++ on this machine)")
    log(f"[native] allocator: {status}")
    return status


# ------------------------------------------------------------- server phase


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 600.0, accept: str | None = None):
    headers = {"Content-Type": "application/json"}
    if accept:
        headers["Accept"] = accept
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers=headers, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def _prom_value(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            if head == name or head.startswith(name + "{"):
                total += float(val)
    return total


def phase_server(model: str, quantize: str | None, kv_quantize: str | None,
                 n_requests: int, prompt_bytes: int, max_new: tuple[int, int],
                 startup_timeout_s: float, extra_env: dict | None = None
                 ) -> dict:
    """Start ``lmrs-serve`` as a child, answer ``n_requests`` concurrent
    chat completions (the last one streamed), read /healthz and /metrics,
    SIGTERM.  More requests than the 8 default slots, with staggered
    budgets, so admissions land mid-decode (mixed steps = ragged spans)."""
    import random

    from lmrs_tpu.utils.platform import child_env

    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "lmrs_tpu.serving.cli", "--backend", "jax",
           "--model", model, "--tokenizer", "byte", "--port", str(port)]
    if quantize:
        cmd += ["--quantize", quantize]
    if kv_quantize:
        cmd += ["--kv-quantize", kv_quantize]
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    server_log = OUT_DIR / "server.log"
    t0 = time.time()
    with open(server_log, "w") as logf:
        proc = subprocess.Popen(cmd, cwd=str(ROOT), stdout=logf,
                                stderr=subprocess.STDOUT,
                                env=child_env(**(extra_env or {})))
    try:
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server child exited rc={proc.returncode} before "
                    f"/healthz:\n{server_log.read_text()[-3000:]}")
            try:
                if _http("GET", f"{base}/healthz", timeout=5)[0] == 200:
                    break
            except OSError:
                pass
            if time.time() - t0 > startup_timeout_s:
                raise RuntimeError("server child not healthy after "
                                   f"{startup_timeout_s:.0f}s")
            time.sleep(1.0)
        t_ready = time.time() - t0
        log(f"[server] healthy after {t_ready:.1f}s (weights init + place)")

        rng = random.Random(SEED)
        lo, hi = max_new
        bodies = [{
            "model": model, "temperature": 0.0,
            "max_tokens": lo + (hi - lo) * i // max(1, n_requests - 1),
            "messages": [
                {"role": "system", "content": "You are a summarizer."},
                {"role": "user", "content": f"[{i:02d}] Summarize: "
                 + _text(rng, prompt_bytes + 50 * (i % 5))}],
        } for i in range(n_requests)]
        bodies[-1].update(stream=True,
                          stream_options={"include_usage": True})
        answers: list = [None] * n_requests

        def ask(i: int) -> None:
            try:
                answers[i] = _http("POST", f"{base}/v1/chat/completions",
                                   bodies[i], timeout=900)
            except Exception as e:  # noqa: BLE001 - reported below
                answers[i] = e

        t1 = time.time()
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(n_requests)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        t_served = time.time() - t1
        completion_tokens = 0
        for i, ans in enumerate(answers):
            if isinstance(ans, Exception) or ans is None:
                raise RuntimeError(f"request {i} failed: {ans!r}")
            status, raw = ans
            assert status == 200, (i, status, raw[:300])
            if bodies[i].get("stream"):
                frames = [ln[len("data: "):] for ln in raw.splitlines()
                          if ln.startswith("data: ")]
                assert frames and frames[-1] == "[DONE]", frames[-2:]
                chunks = [json.loads(f) for f in frames[:-1]]
                assert all(c["object"] == "chat.completion.chunk"
                           for c in chunks)
                fin = [c for c in chunks if c.get("choices")
                       and c["choices"][0].get("finish_reason")]
                assert fin and fin[-1]["choices"][0]["finish_reason"] in (
                    "stop", "length"), fin[-1:]
                usage = [c["usage"] for c in chunks if c.get("usage")][-1]
                n_stream_frames = len(chunks)
            else:
                doc = json.loads(raw)
                assert doc["object"] == "chat.completion", doc
                assert doc["choices"][0]["finish_reason"] in (
                    "stop", "length"), doc["choices"][0]
                usage = doc["usage"]
            assert usage["completion_tokens"] > 0, (i, usage)
            assert usage["prompt_tokens"] >= prompt_bytes, (i, usage)
            completion_tokens += usage["completion_tokens"]
        log(f"[server] {n_requests} requests answered ({n_requests - 1} "
            f"concurrent + 1 streamed in {n_stream_frames} frames), "
            f"{completion_tokens} completion tokens, first-wave wall "
            f"{t_served:.1f}s (compiles included)")

        assert _http("GET", f"{base}/healthz", timeout=30)[0] == 200
        eng = json.loads(_http("GET", f"{base}/metrics")[1])["engine"]
        prom = _http("GET", f"{base}/metrics", accept="text/plain")[1]
        group = _prom_value(prom, "lmrs_decode_group_occupancy_ratio_count")
        spans = eng["rpa"]["dispatches"]
        log(f"[server] /metrics: decode_tokens={eng['decode_tokens']} "
            f"prefill_tokens={eng['prefill_tokens']} "
            f"rpa_dispatches={spans} row_group_dispatches={int(group)} "
            f"mixed_dispatches={eng['mixed_batch']['dispatches']}")
        assert eng["decode_tokens"] > 0, eng
        assert spans > 0, "no ragged-span dispatch ran in the server"
        assert group > 0, "no row-group decode dispatch ran in the server"

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        assert rc == 0, f"server child exit code {rc} on SIGTERM"
        log("[server] clean SIGTERM, exit 0")
        return {"ready_s": round(t_ready, 1), "serve_s": round(t_served, 1),
                "completion_tokens": completion_tokens}
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)


# ----------------------------------------------------- kernel-parity phase


def _maxdiff(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def check_flash_prefill(interpret: bool) -> float:
    """Flash kernel vs XLA reference on ragged bf16 prefill."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.attention import attention
    from lmrs_tpu.ops.flash_attention import flash_attention

    b, s, h, kh, hd = 2, 512, 8, 4, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.bfloat16)
    lengths = jnp.asarray([s, 300], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    got = flash_attention(q, k, v, lengths, interpret=interpret)
    want = attention(q, k, v, positions, lengths)
    # compare valid rows only (flash zeroes padded-q rows by design)
    row_ok = (positions < lengths[:, None])[..., None, None]
    return _maxdiff(jnp.where(row_ok, got, 0), jnp.where(row_ok, want, 0))


def check_packed_prefill(interpret: bool) -> float:
    """Segment-masked flash vs the packed XLA reference."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.attention import packed_attention
    from lmrs_tpu.ops.flash_attention import flash_attention

    b, s, h, kh, hd = 1, 512, 8, 4, 128
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.bfloat16)
    seg = np.full((b, s), -1, np.int32)  # three segments + padded tail
    seg[0, :200], seg[0, 200:330], seg[0, 330:470] = 0, 1, 2
    seg_ids = jnp.asarray(seg)
    lengths = jnp.asarray([470], jnp.int32)
    got = flash_attention(q, k, v, lengths, interpret=interpret,
                          segment_ids=seg_ids)
    want = packed_attention(q, k, v, seg_ids, lengths)
    valid = (seg_ids >= 0)[..., None, None]
    return _maxdiff(jnp.where(valid, got, 0), jnp.where(valid, want, 0))


def check_fused_ragged_decode(interpret: bool, row_group: int = 1) -> float:
    """Write-fused ragged decode (kv heads folded in-kernel, ``row_group``
    rows per program) vs XLA scatter + gather, ragged lengths spanning page
    boundaries and the 8-row RMW window."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import (paged_decode_pallas_fused,
                                              paged_decode_xla)

    b, h, kh, hd, ps, w = 6, 8, 4, 128, 128, 4
    n_pages = 1 + b * w
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((b, kh, hd)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((b, kh, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), jnp.bfloat16)
    # distinct pages per row; page 0 reserved as the null page
    tables = jnp.asarray(1 + np.arange(b * w).reshape(b, w), jnp.int32)
    # first-page partial / exact boundary / mid window + odd offset / ...
    kv_lens = jnp.asarray([5, ps, 2 * ps + 77, 3 * ps + 1, 9, 4 * ps],
                          jnp.int32)
    got, kp_out, vp_out = paged_decode_pallas_fused(
        q, k_new, v_new, kp, vp, tables, kv_lens, interpret=interpret,
        row_group=row_group)
    pos = np.asarray(kv_lens) - 1
    kp_ref, vp_ref = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    for i in range(b):
        page = int(np.asarray(tables)[i, pos[i] // ps])
        kp_ref[page, :, pos[i] % ps] = np.asarray(k_new, np.float32)[i]
        vp_ref[page, :, pos[i] % ps] = np.asarray(v_new, np.float32)[i]
    kp_ref = jnp.asarray(kp_ref, jnp.bfloat16)
    vp_ref = jnp.asarray(vp_ref, jnp.bfloat16)
    want = paged_decode_xla(q, kp_ref, vp_ref, tables, kv_lens)
    d = _maxdiff(got, want)
    # the in-place write must land exactly; untouched pages stay intact
    d = max(d, _maxdiff(kp_out[1:], kp_ref[1:]))
    return max(d, _maxdiff(vp_out[1:], vp_ref[1:]))


def check_multi_token_verify(interpret: bool) -> float:
    """Ragged multi-token verify vs the XLA scatter+gather reference, spans
    straddling page and RMW-window boundaries."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import (paged_decode_multi_xla,
                                              paged_decode_pallas_multi)

    b, t, h, kh, hd, ps, n_pages = 2, 5, 8, 4, 128, 128, 12
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(b * 3).reshape(b, 3), jnp.int32)
    kv_lens = jnp.asarray([ps + 2, 131], jnp.int32)
    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, kp, vp, tables, kv_lens)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, kp, vp, tables, kv_lens, interpret=interpret)
    d = _maxdiff(got, want)
    d = max(d, _maxdiff(k_out[1:1 + b * 3], k_ref[1:1 + b * 3]))
    return max(d, _maxdiff(v_out[1:1 + b * 3], v_ref[1:1 + b * 3]))


def check_int8_forward(interpret: bool) -> float:
    """Weights-only int8 through the full forward: finite logits that stay
    correlated with the bf16 forward (a lowering check, not a numerics
    gate)."""
    del interpret
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.config import ModelConfig
    from lmrs_tpu.models.transformer import forward, init_params
    from lmrs_tpu.ops.quant import quantize_params

    cfg = ModelConfig(vocab_size=512, dim=256, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=512, max_seq_len=256,
                      dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(1, 255, (1, 128)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(128)[None], (1, 128))
    base, _ = forward(params, cfg, tokens, positions)
    q8, _ = forward(quantize_params(params), cfg, tokens, positions)
    assert bool(jnp.all(jnp.isfinite(q8))), "int8 forward produced non-finite"
    corr = float(jnp.corrcoef(base.ravel(), q8.ravel())[0, 1])
    assert corr > 0.98, f"int8 forward decorrelated from bf16 ({corr:.3f})"
    return 1.0 - corr


def check_int8_kv_decode(interpret: bool, row_group: int = 1) -> float:
    """Int8 KV pools through the dequantizing fused decode kernel (32-row
    RMW windows) vs the int8 XLA scatter+gather path."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import (paged_decode_pallas_fused,
                                              paged_decode_xla)
    from lmrs_tpu.ops.quant import kv_quant

    rng = np.random.default_rng(9)
    B, H, K, hd, ps, P, W = 8, 16, 8, 128, 512, 40, 4
    kq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    tables = jnp.asarray(
        rng.permutation(P - 1)[: B * W].reshape(B, W) + 1, jnp.int32)
    lens = jnp.asarray(rng.integers(33, W * ps, (B,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    kn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.bfloat16)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)
    got, kq1, vq1 = paged_decode_pallas_fused(
        q, kn, vn, kq, vq, tables, lens, interpret=interpret,
        kscale=ks, vscale=vs, row_group=row_group)
    pos = lens - 1
    page = jnp.take_along_axis(tables, (pos // ps)[:, None], 1)[:, 0]
    off = pos % ps
    kq_ref = kq.at[page, :, off].set(
        kv_quant(kn[:, None].astype(jnp.float32), ks)[:, 0])
    vq_ref = vq.at[page, :, off].set(
        kv_quant(vn[:, None].astype(jnp.float32), vs)[:, 0])
    want = paged_decode_xla(q, kq_ref, vq_ref, tables, lens,
                            kv_scales=(ks, vs))
    wdiff = int(jnp.sum(kq1 != kq_ref)) + int(jnp.sum(vq1 != vq_ref))
    assert wdiff == 0, f"{wdiff} pool bytes differ from the XLA scatter"
    return _maxdiff(got, want)


def check_int8_multi_verify(interpret: bool) -> float:
    """Int8 pools through the dequantizing MULTI-token verify kernel vs the
    int8 XLA multi path."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import (paged_decode_multi_xla,
                                              paged_decode_pallas_multi)

    b, t, h, kh, hd, ps, n_pages = 2, 5, 8, 4, 128, 128, 12
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((b, t, kh, hd)), jnp.bfloat16)
    kq = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)), jnp.int8)
    tables = jnp.asarray(1 + np.arange(b * 3).reshape(b, 3), jnp.int32)
    kv_lens = jnp.asarray([ps + 2, 131], jnp.int32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, kq, vq, tables, kv_lens, kv_scales=(ks, vs))
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, kq, vq, tables, kv_lens, interpret=interpret,
        kscale=ks, vscale=vs)
    wdiff = int(jnp.sum(k_out[1:1 + b * 3] != k_ref[1:1 + b * 3])) \
        + int(jnp.sum(v_out[1:1 + b * 3] != v_ref[1:1 + b * 3]))
    assert wdiff == 0, f"{wdiff} pool bytes differ from the XLA scatter"
    return _maxdiff(got, want)


def check_ragged_spans(interpret: bool, int8: bool = False) -> float:
    """``ragged_spans_pallas`` on a MIXED span list — decode rows, a
    prefill-slice row whose length is not a tile multiple, an inactive
    row, and a span long enough for the wide query tile (two tiles, the
    second sliding back over the first) — vs ``ragged_spans_xla``: in-span
    outputs and every row's valid pool prefix (past it lies the narrow
    path's future-position padding)."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import (pack_spans, ragged_spans_pallas,
                                              ragged_spans_xla)

    q_lens = np.asarray([1, 45, 1, 0, 1, 300], np.int32)
    b, h, kh, hd, ps, w = len(q_lens), 8, 4, 128, 128, 3
    bases = np.asarray([200, 7, 0, 0, ps - 1, 64], np.int32)
    n_pages = 1 + b * w
    qs, total = pack_spans(q_lens)
    rng = np.random.default_rng(13)
    dt = jnp.bfloat16
    qf = jnp.asarray(rng.standard_normal((total, h, hd)), dt)
    knf = jnp.asarray(rng.standard_normal((total, kh, hd)), dt)
    vnf = jnp.asarray(rng.standard_normal((total, kh, hd)), dt)
    if int8:
        kp = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
        kw = dict(kscale=ks, vscale=vs)
        xkw = dict(kv_scales=(ks, vs))
    else:
        kp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), dt)
        vp = jnp.asarray(rng.standard_normal((n_pages, kh, ps, hd)), dt)
        kw, xkw = {}, {}
    tables = jnp.asarray(1 + np.arange(b * w).reshape(b, w), jnp.int32)
    row_flat = np.full((total,), b, np.int32)
    for i, (s, n) in enumerate(zip(qs, q_lens)):
        row_flat[s:s + n] = i
    args = (qf, knf, vnf, kp, vp, tables, jnp.asarray(bases),
            jnp.asarray(qs), jnp.asarray(q_lens))
    got, k_out, v_out = ragged_spans_pallas(*args, interpret=interpret, **kw)
    want, k_ref, v_ref = ragged_spans_xla(*args, jnp.asarray(row_flat), **xkw)
    in_span = jnp.asarray(row_flat < b)[:, None, None]
    d = _maxdiff(jnp.where(in_span, got, 0), jnp.where(in_span, want, 0))
    upto = bases + q_lens

    def windows(pool):
        win = np.asarray(pool.astype(jnp.float32))[np.asarray(tables)]
        win = win.transpose(0, 1, 3, 2, 4).reshape(b, w * ps, kh, hd)
        return [win[i, :int(u)] for i, u in enumerate(upto)]

    for got_pool, ref_pool in ((k_out, k_ref), (v_out, v_ref)):
        for g, r in zip(windows(got_pool), windows(ref_pool)):
            if g.size:
                # bf16/int8 writes are copies / the one shared quant rule
                d = max(d, float(np.max(np.abs(g - r))) if not int8
                        else float(np.sum(g != r)))
    return d


KERNEL_CHECKS = [
    # (name, fn, kwargs, tolerance)
    ("flash_prefill_vs_xla", check_flash_prefill, {}, 0.03),
    ("packed_prefill_vs_xla", check_packed_prefill, {}, 0.03),
    ("fused_ragged_decode_vs_xla", check_fused_ragged_decode, {}, 0.03),
    ("fused_ragged_decode_g4_vs_xla", check_fused_ragged_decode,
     {"row_group": 4}, 0.03),
    ("multi_token_verify_vs_xla", check_multi_token_verify, {}, 0.03),
    ("int8_forward", check_int8_forward, {}, 0.02),
    # tol 0.1 on the int8 checks: the XLA reference dequantizes int8*scale
    # INTO bf16 before its einsums (double rounding) while the kernels fold
    # the scales in f32 — the gap is reference precision, not kernel error
    ("int8_kv_fused_decode_vs_xla", check_int8_kv_decode, {}, 0.1),
    ("int8_kv_fused_decode_g4_vs_xla", check_int8_kv_decode,
     {"row_group": 4}, 0.1),
    ("int8_multi_verify_vs_xla", check_int8_multi_verify, {}, 0.1),
    ("ragged_spans_vs_xla", check_ragged_spans, {}, 0.03),
    # dequantized values reach 127 * 0.05 = 6.35, where one bf16 ulp is
    # 0.031: 0.15 is ~5 ulp of the double-rounding reference
    ("ragged_spans_int8_vs_xla", check_ragged_spans, {"int8": True}, 0.15),
]


def phase_kernel_parity(interpret: bool = False) -> dict:
    """Every Pallas kernel vs its XLA reference.  A check that raises
    (e.g. a Mosaic lowering error) propagates: nothing is caught."""
    results = {}
    for name, fn, kwargs, tol in KERNEL_CHECKS:
        t0 = time.time()
        diff = fn(interpret, **kwargs)
        dt = time.time() - t0
        results[name] = round(diff, 5)
        log(f"[kernels] {'PASS' if diff <= tol else 'FAIL'} {name}: "
            f"diff={diff:.5f} tol={tol} ({dt:.1f}s)")
        assert diff <= tol, f"{name}: diff {diff} > tol {tol}"
    return results


# ------------------------------------------------------- in-process phases


class _CacheCounter:
    """Counts persistent-compilation-cache lookups and hits (JAX monitoring
    events) so each phase can print what it compiled vs loaded."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        mon.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> str:
        out = (f"compile requests {self.requests}, persistent-cache hits "
               f"{self.hits}, compiled fresh {self.requests - self.hits}")
        self.requests = self.hits = 0
        return out


def _peak_bytes() -> list[int]:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def _release(engine) -> None:
    """Shut an engine down and free its page pool NOW (params stay: the
    caller holds them) — two 8B-shape pools do not fit beside the weights."""
    engine.shutdown()
    sched = engine._scheduler
    for buf in (sched.cache.k, sched.cache.v, sched.kscale, sched.vscale):
        if buf is not None:
            buf.delete()
    engine._scheduler = engine._runner = None
    gc.collect()


def phase_mapreduce(model: str, quantize: str | None, kv_quantize: str | None,
                    n_segments: int, chunk_tokens: int, max_new: int):
    """The README main path: ``lmrs_tpu.cli.main`` in process, ``--backend
    jax --report``, on a seeded synthetic transcript.  Returns (report,
    engine) — the engine the CLI built (recorded as ``make_engine`` hands
    it over; nothing about the run is changed) so the next phase can reuse
    its device-resident params."""
    import lmrs_tpu.pipeline as pipeline_mod
    from lmrs_tpu import cli

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    src, dst = OUT_DIR / "transcript.json", OUT_DIR / "summary.txt"
    src.write_text(json.dumps(synth_transcript(SEED, n_segments)))
    argv = ["--input", str(src), "--output", str(dst), "--backend", "jax",
            "--model", model, "--tokenizer", "byte", "--report", "--quiet",
            "--max-tokens-per-chunk", str(chunk_tokens)]
    if quantize:
        argv += ["--quantize", quantize]
    if kv_quantize:
        argv += ["--kv-quantize", kv_quantize]
    built = []
    real_make = pipeline_mod.make_engine

    def recording_make(*a, **kw):
        built.append(real_make(*a, **kw))
        return built[-1]

    os.environ["MAX_TOKENS"] = str(max_new)  # the CLI's generation budget
    pipeline_mod.make_engine = recording_make
    t0 = time.time()
    try:
        rc = cli.main(argv)
    finally:
        pipeline_mod.make_engine = real_make
    wall = time.time() - t0
    assert rc == 0, f"lmrs CLI exit code {rc}"
    report = json.loads(Path(str(dst) + ".report.json").read_text())
    summary = dst.read_text()
    engine = built[0]
    sched = engine._scheduler
    em = report["engine_metrics"]
    log(f"[mapreduce] lmrs CLI wall {wall:.1f}s: "
        f"{report['num_input_segments']} segments -> "
        f"{report['num_chunks']} chunks, "
        f"requests {report['total_requests']} "
        f"(failed {report['failed_requests']}), "
        f"stage_times {report['stage_times']}, "
        f"prefill_tokens {em['prefill_tokens']} decode_tokens "
        f"{em['decode_tokens']}, summary {len(summary)} chars")
    assert report["failed_requests"] == 0, report["failed_requests"]
    assert report["num_chunks"] >= 2, report["num_chunks"]
    assert {"map", "reduce"} <= set(report["stage_times"]), \
        report["stage_times"]
    assert em["decode_tokens"] > 0 and em["prefill_tokens"] > 0, em
    # a wedge or a blown deadline is a failure here, never a retry to hide
    assert em["deadline_exceeded"] == 0 and em["shed"] == 0, em
    assert not engine.wedged(), "engine degraded (watchdog wedge)"
    assert isinstance(summary, str)
    # kernels must still be armed: nothing may have moved the engine off
    # its Pallas paths during the run
    assert sched._use_ragged and sched._use_flash, (
        f"kernel gates after the run: ragged={sched._use_ragged} "
        f"flash={sched._use_flash}")
    log(f"[mapreduce] kernel gates after the run: ragged="
        f"{sched._use_ragged} flash={sched._use_flash} row_group="
        f"{sched._row_group} group_dispatches="
        f"{sched.metrics['group_dispatches']} rpa_dispatches="
        f"{sched.metrics['rpa_dispatches']}")
    assert sched.metrics["group_dispatches"] > 0
    return report, engine


def _id_tokenizer():
    """Byte tokenizer whose ``decode`` spells out every token id: results
    carry text only, and a random-weight model over a 128k vocabulary
    almost never emits an id the byte decoder would keep."""
    from lmrs_tpu.data.tokenizer import ByteTokenizer

    class IdTokenizer(ByteTokenizer):
        def decode(self, ids):
            return "".join(f"<{int(i)}>" for i in ids)

    return IdTokenizer()


def _greedy(engine, prompts: list[str], max_new: int) -> list[list[int]]:
    """Greedy token ids per prompt (engine built with ``_id_tokenizer``)."""
    import re

    from lmrs_tpu.engine.api import GenerationRequest

    reqs = [GenerationRequest(prompt=p, request_id=i, temperature=0.0,
                              max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    out = sorted(engine.generate_batch(reqs), key=lambda r: r.request_id)
    toks = []
    for r in out:
        assert r.error is None and r.finish_reason in ("stop", "length"), (
            r.request_id, r.finish_reason, r.error)
        ids = [int(x) for x in re.findall(r"<(\d+)>", r.text)]
        assert ids and len(ids) == r.completion_tokens, (r.text, ids)
        toks.append(ids)
    return toks


def compare_kernels_vs_xla(build_engine, n_prompts: int, prompt_bytes: int,
                           max_new: int, min_agree: float,
                           tag: str = "kernels-vs-xla") -> float:
    """Greedy tokens of ``n_prompts`` prompts on the kernel path vs a second
    scheduler whose two gates are set to the XLA path HERE, before its first
    dispatch.  ``build_engine()`` must return a fresh engine on the same
    device-resident params each call.

    Stated tolerance: bf16 kernels and bf16 XLA attention round differently,
    and a greedy argmax over a random-weight model's near-flat logits can
    flip on that noise and then diverge for good, so the gate is agreement
    of the FIRST token of every prompt (one prefill, no feedback) and a
    mean common-prefix share of at least ``min_agree`` (first chip run,
    PR 22: 4/4 first tokens, shares 1.0/0.5/0.38/0.5); a wrong kernel
    agrees on ~1/vocab of tokens."""
    import random

    rng = random.Random(SEED + 1)
    prompts = [f"[{i:02d}] Summarize: " + _text(rng, prompt_bytes + 37 * i)
               for i in range(n_prompts)]
    eng_k = build_engine()
    sk = eng_k._scheduler
    assert sk._use_ragged and sk._use_flash, "kernel path not selected"
    toks_k = _greedy(eng_k, prompts, max_new)
    assert sk._use_ragged and sk._use_flash
    _release(eng_k)

    eng_x = build_engine()
    sx = eng_x._scheduler
    sx._use_ragged = False  # XLA paged decode / span attention
    sx._use_flash = False   # XLA prefill attention
    toks_x = _greedy(eng_x, prompts, max_new)
    _release(eng_x)

    shares, first_ok = [], 0
    for a, b in zip(toks_k, toks_x):
        n = 0
        while n < min(len(a), len(b)) and a[n] == b[n]:
            n += 1
        shares.append(n / max(1, min(len(a), len(b))))
        first_ok += bool(a and b and a[0] == b[0])
    mean = sum(shares) / len(shares)
    log(f"[{tag}] greedy {max_new} tokens x {n_prompts} prompts: first "
        f"token equal {first_ok}/{n_prompts}, common-prefix share per "
        f"prompt {[round(s, 2) for s in shares]}, mean {mean:.3f} "
        f"(gate: all first tokens, mean >= {min_agree})")
    assert first_ok == n_prompts, (toks_k, toks_x)
    assert mean >= min_agree, (toks_k, toks_x)
    return mean


def phase_kernels_vs_xla(engine, n_prompts: int, prompt_bytes: int,
                         max_new: int, min_agree: float) -> float:
    """Single-chip comparison on the params the map-reduce engine placed."""
    import dataclasses

    from lmrs_tpu.engine.jax_engine import JaxEngine

    params, model_cfg = engine.params, engine.model_cfg
    # params are already quantized + placed: the rebuilds take them as is
    cfg = dataclasses.replace(engine.cfg, quantize=None, max_tokens=max_new)
    _release(engine)

    def build():
        return JaxEngine(cfg, model_cfg, params=params,
                         tokenizer=_id_tokenizer())

    return compare_kernels_vs_xla(build, n_prompts, prompt_bytes, max_new,
                                  min_agree)


# --------------------------------------------------------- four-chip phases


def _shard_report(tag: str, tree, n_dev: int, sharded_share: float) -> None:
    """Assert from ``addressable_shards`` that ``tree``'s bytes are spread
    over ``n_dev`` devices, about 1/n_dev each (replicated leaves — norms,
    scales — are tiny), not piled on device 0."""
    import jax

    per_dev: dict[int, int] = {}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.size * leaf.dtype.itemsize
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                     + sh.data.size * sh.data.dtype.itemsize)
    shares = {d: round(b / total, 3) for d, b in sorted(per_dev.items())}
    log(f"[{tag}] {total / 2**30:.2f} GiB logical, per-device share of the "
        f"logical bytes {shares}")
    assert len(per_dev) == n_dev, per_dev
    for d, s in shares.items():
        assert 1 / n_dev - 0.02 <= s <= sharded_share, (d, s)


def _decode_program_text(sched) -> str:
    """Optimized HLO of one decode-block program, built by the scheduler's
    own builder and lowered on example arguments of its dispatch shapes."""
    import jax
    import jax.numpy as jnp

    B = sched.B
    w = sched._decode_window([], sched.decode_block)[0]
    args = (sched.params, sched.cache.k, sched.cache.v, sched.kscale,
            sched.vscale, jnp.arange(B, dtype=jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.int32),
            jnp.zeros((B, w), jnp.int32), jnp.zeros((B,), bool),
            jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32))
    return sched._get_decode_fn(w).lower(*args).compile().as_text()


def phase_tp(model_cfg, tp: int, slots: int, n_prompts: int,
             prompt_bytes: int, max_new: int, min_agree: float) -> None:
    """``model_cfg`` on ``MeshConfig(tp=tp)`` through JaxEngine and the
    continuous scheduler: shard_map-wrapped kernels vs the XLA attention
    path on the SAME mesh and params; shard placement and the decode
    program's collectives asserted."""
    import jax

    from lmrs_tpu.config import EngineConfig, MeshConfig
    from lmrs_tpu.engine.jax_engine import JaxEngine

    n_dev = len(jax.devices())
    assert n_dev >= tp, (n_dev, tp)
    mesh_cfg = MeshConfig(dp=1, tp=tp)
    cfg = EngineConfig(backend="jax", scheduler="continuous", seed=SEED,
                       max_tokens=max_new, max_batch_slots=slots,
                       tokenizer="byte", retry_delay=0.0)
    t0 = time.time()
    first = JaxEngine(cfg, model_cfg, mesh_cfg=mesh_cfg,
                      tokenizer=_id_tokenizer(), devices=jax.devices()[:tp])
    log(f"[tp{tp}] {model_cfg.name} {model_cfg.dtype} L={model_cfg.n_layers} "
        f"params placed + scheduler built in "
        f"{time.time() - t0:.1f}s")
    params = first.params
    sched = first._scheduler
    _shard_report(f"tp{tp} params", params, tp, 1 / tp + 0.02)
    _shard_report(f"tp{tp} page pool", (sched.cache.k, sched.cache.v), tp,
                  1 / tp + 0.02)
    hlo = _decode_program_text(sched)
    n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    n_ag = hlo.count("all-gather(") + hlo.count("all-gather-start(")
    n_kernel = hlo.count("tpu_custom_call")
    log(f"[tp{tp}] decode program: all-reduce x{n_ar}, all-gather x{n_ag}, "
        f"tpu_custom_call x{n_kernel}")
    # row-parallel wo and w_down: one psum each per layer (scanned: >= 2
    # in the text); the Pallas decode kernel must be in the program
    assert n_ar >= 2, "expected tp all-reduces in the decode program"
    if jax.devices()[0].platform == "tpu":
        assert n_kernel >= 1, "no Pallas kernel in the tp decode program"
    engines = [first]

    def build():
        if engines:
            return engines.pop()
        return JaxEngine(cfg, model_cfg, mesh_cfg=mesh_cfg, params=params,
                         tokenizer=_id_tokenizer(),
                         devices=jax.devices()[:tp])

    compare_kernels_vs_xla(build, n_prompts, prompt_bytes, max_new,
                           min_agree, tag=f"tp{tp} kernels-vs-xla")
    log(f"[tp{tp}] peak bytes per device {_peak_bytes()}")
    del params, first, sched
    gc.collect()


def phase_replicas(model_cfg, dp: int, tp: int, slots: int, n_requests: int,
                   prompt_bytes: int, max_new: int) -> None:
    """dp x tp ``ReplicatedEngine`` (replicas are threads of ONE process on
    explicit device lists): every replica must serve traffic on its own
    devices."""
    import random

    import jax

    from lmrs_tpu.config import EngineConfig, MeshConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.replicated import ReplicatedEngine

    cfg = EngineConfig(backend="jax", scheduler="continuous", seed=SEED,
                       max_tokens=max_new, max_batch_slots=slots,
                       tokenizer="byte", retry_delay=0.0)
    t0 = time.time()
    engine = ReplicatedEngine(cfg, model_cfg, MeshConfig(dp=dp, tp=tp),
                              devices=jax.devices()[:dp * tp])
    log(f"[dp{dp}xtp{tp}] {model_cfg.name} L={model_cfg.n_layers} built in "
        f"{time.time() - t0:.1f}s")
    try:
        rng = random.Random(SEED + 2)
        reqs = [GenerationRequest(
            prompt=f"[{i:02d}] Summarize: " + _text(rng, prompt_bytes),
            request_id=i, temperature=0.0, max_new_tokens=max_new)
            for i in range(n_requests)]
        out = engine.generate_batch(reqs)
        assert sorted(r.request_id for r in out) == list(range(n_requests))
        for r in out:
            assert r.error is None and r.completion_tokens > 0, (
                r.request_id, r.finish_reason, r.error)
        seen: set[int] = set()
        for i, rep in enumerate(engine.replicas):
            sched = rep._scheduler
            devs = {sh.device.id for sh in sched.cache.k.addressable_shards}
            log(f"[dp{dp}xtp{tp}] replica {i}: devices {sorted(devs)}, "
                f"decode_tokens {sched.metrics['decode_tokens']}, gates "
                f"ragged={sched._use_ragged} flash={sched._use_flash}")
            assert len(devs) == tp and not (devs & seen), (devs, seen)
            seen |= devs
            assert sched.metrics["decode_tokens"] > 0, f"replica {i} idle"
            assert sched._use_ragged and sched._use_flash
        assert len(seen) == dp * tp
    finally:
        engine.shutdown()
    log(f"[dp{dp}xtp{tp}] peak bytes per device {_peak_bytes()}")


# ---------------------------------------------------------------------- main


def _same_device(dev: dict) -> None:
    """The in-process backend must be the device the probe child saw."""
    import jax

    d = jax.devices()
    got = {"platform": d[0].platform, "kind": d[0].device_kind,
           "count": len(d)}
    assert got == dev, (got, dev)


def run_one_chip(dev: dict) -> None:
    model, quantize, kv_quantize = "bench-8b", "int8", "int8"
    rebuild_native()
    # phase order: the server CHILD first, while this process is off JAX;
    # the in-process phases after it has exited (one process per chip).
    # The child compiles cold and fills the cache the later phases hit.
    t0 = time.time()
    phase_server(model, quantize, kv_quantize, n_requests=12,
                 prompt_bytes=1100, max_new=(32, 64), startup_timeout_s=600)
    log(f"[server] phase total {time.time() - t0:.1f}s")

    import jax

    from lmrs_tpu.utils.platform import on_tpu, setup_compile_cache

    setup_compile_cache()
    counter = _CacheCounter()
    assert on_tpu(), jax.devices()
    t0 = time.time()
    phase_kernel_parity()
    log(f"[kernels] phase total {time.time() - t0:.1f}s; {counter.take()}")
    t0 = time.time()
    _report, engine = phase_mapreduce(model, quantize, kv_quantize,
                                      n_segments=170, chunk_tokens=1200,
                                      max_new=48)
    log(f"[mapreduce] phase total {time.time() - t0:.1f}s (set-up: weights "
        f"init + place + compiles); {counter.take()}")
    log(f"[mapreduce] peak_bytes_in_use {_peak_bytes()}")
    t0 = time.time()
    # ~0.9k-token prompts: the XLA reference attention materialises
    # [B, H, S, S] scores, which at S=2048 would not fit beside the model
    phase_kernels_vs_xla(engine, n_prompts=4, prompt_bytes=800, max_new=8,
                         min_agree=0.3)
    log(f"[kernels-vs-xla] phase total {time.time() - t0:.1f}s; "
        f"{counter.take()}")
    log(f"[device] peak_bytes_in_use {_peak_bytes()} of "
        f"{[int((d.memory_stats() or {}).get('bytes_limit', -1)) for d in jax.devices()]}")
    _same_device(dev)


def run_four_chips(dev: dict) -> None:
    import dataclasses

    import jax

    from lmrs_tpu.config import model_preset
    from lmrs_tpu.utils.platform import on_tpu, setup_compile_cache

    rebuild_native()
    setup_compile_cache()
    counter = _CacheCounter()
    assert on_tpu(), jax.devices()
    # llama3-8b at its published widths, bf16 (16 GB: needs the four
    # chips); serving window cut to 2048 like bench-8b (the page pool,
    # not a width)
    full = dataclasses.replace(model_preset("llama3-8b"), max_seq_len=2048)
    t0 = time.time()
    phase_tp(full, tp=4, slots=8, n_prompts=4, prompt_bytes=800, max_new=8,
             min_agree=0.3)
    log(f"[tp4] phase total {time.time() - t0:.1f}s; {counter.take()}")
    # same widths, depth cut to 8 layers so one replica fits two chips
    cut = dataclasses.replace(full, n_layers=8)
    log("[dp2xtp2] cut: n_layers 32 -> 8 (two chips per replica); widths "
        "unchanged")
    t0 = time.time()
    phase_replicas(cut, dp=2, tp=2, slots=4, n_requests=8, prompt_bytes=900,
                   max_new=16)
    log(f"[dp2xtp2] phase total {time.time() - t0:.1f}s; {counter.take()}")
    _same_device(dev)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the four-chip path (tp=4 and "
                         "dp=2 x tp=2); default: the one-chip main path")
    args = ap.parse_args(argv)
    if not __debug__:
        raise RuntimeError("chip_smoke.py checks with assert statements: "
                           "run it without -O")
    t_all = time.time()
    dev = probe_device()
    log(f"[device] {dev}")
    require_tpu(dev, args.chips)
    if args.chips == 4:
        run_four_chips(dev)
    else:
        run_one_chip(dev)
    log(f"[done] all phases passed in {time.time() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
