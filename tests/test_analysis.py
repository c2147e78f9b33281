"""lmrs-lint analyzer tests: planted-fixture positives, clean negatives,
golden finding output, baseline add/expire semantics, the repo-clean CI
gate, and regression tests for the real findings the first run surfaced
(router host-counter lost updates, Tracer.recorded increments, the env
parser's NaN/empty-string discipline)."""

from __future__ import annotations

import json
import threading
from pathlib import Path

import pytest

from lmrs_tpu.analysis import (Baseline, Module, RepoContext, run_passes,
                               run_repo)
from lmrs_tpu.analysis import drift, envpass, locks, tracing

REPO_ROOT = Path(__file__).resolve().parents[1]


def ctx_for(sources: dict[str, str], docs: dict[str, str] | None = None
            ) -> RepoContext:
    mods = [Module.from_source(p, s) for p, s in sources.items()]
    return RepoContext(root=REPO_ROOT, modules=mods, docs=dict(docs or {}))


def rules(findings) -> set[str]:
    return {f.rule for f in findings}


# --------------------------------------------------------------- race pass

RACE_POSITIVE = '''
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._pinned = {}  # guarded-by: _lock
        self.count = 0  # guarded-by: _lock

    def bad_write(self, k, v):
        self._pinned[k] = v          # write without the lock

    def bad_increment(self):
        self.count += 1              # lost-update RMW

    def bad_mutator(self, k):
        self._pinned.pop(k, None)    # mutator call without the lock
'''

RACE_NEGATIVE = '''
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._pinned = {}  # guarded-by: _lock
        self.count = 0  # guarded-by: _lock

    def good_write(self, k, v):
        with self._lock:
            self._pinned[k] = v
            self.count += 1

    def reads_are_fine(self):
        return dict(self._pinned)

    def _helper(self, k):  # holds-lock: _lock
        self._pinned.pop(k, None)
'''


def test_race_unguarded_writes_detected():
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": RACE_POSITIVE}))
    unguarded = [f for f in findings if f.rule == "race.unguarded-write"]
    assert len(unguarded) == 3
    lines = {f.line for f in unguarded}
    assert len(lines) == 3  # one per planted site, each with a location
    assert all("with _lock" in f.message for f in unguarded)
    assert all("guarded-by declared" in f.hint for f in unguarded)


def test_race_clean_equivalent_is_silent():
    assert locks.run(ctx_for({"lmrs_tpu/x.py": RACE_NEGATIVE})) == []


def test_race_comment_above_annotation_binds_to_next_line():
    """The standalone-comment form: `# guarded-by:` on its own line
    directly above the attribute's defining line (used when the defining
    line is too long for a trailer) must register — a silently-ignored
    annotation is worse than none."""
    src = '''
import threading

class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._deferred = []

    def bad(self, item):
        self._deferred.append(item)
'''
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": src}))
    assert [f.rule for f in findings] == ["race.unguarded-write"]
    assert "_deferred" in findings[0].message


def test_race_module_level_guarded_global():
    src = '''
import threading

_lock = threading.Lock()
_last = {}  # guarded-by: _lock

def bad(reason, t):
    _last[reason] = t

def good(reason, t):
    with _lock:
        _last[reason] = t
'''
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": src}))
    assert [f.rule for f in findings] == ["race.unguarded-write"]
    assert findings[0].line == 8


def test_race_lock_order_cycle_detected():
    src = '''
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._b:
            with self._a:
                pass
'''
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": src}))
    assert "race.lock-order-cycle" in rules(findings)


def test_race_consistent_order_no_cycle():
    src = '''
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def m1(self):
        with self._a:
            with self._b:
                pass

    def m2(self):
        with self._a:
            with self._b:
                pass
'''
    assert locks.run(ctx_for({"lmrs_tpu/x.py": src})) == []


def test_race_cycle_via_same_class_call():
    src = '''
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def outer(self):
        with self._a:
            self.inner()

    def inner(self):
        with self._b:
            pass

    def other(self):
        with self._b:
            with self._a:
                pass
'''
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": src}))
    assert "race.lock-order-cycle" in rules(findings)


def test_race_blocking_under_lock():
    src = '''
import os
import time
import threading

class J:
    def __init__(self):
        self._lock = threading.Lock()

    def bad(self, fh):
        with self._lock:
            time.sleep(0.1)
            os.fsync(fh.fileno())

    def good(self, fh):
        with self._lock:
            data = fh.name
        time.sleep(0.1)
        return data
'''
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": src}))
    blocking = [f for f in findings
                if f.rule == "race.blocking-under-lock"]
    assert len(blocking) == 2  # sleep + fsync, nothing from good()


def test_race_inline_suppression():
    src = '''
import os
import threading

class J:
    def __init__(self):
        self._lock = threading.Lock()

    def append(self, fh):
        with self._lock:
            os.fsync(fh.fileno())  # lint: ignore[race.blocking-under-lock]
'''
    ctx = ctx_for({"lmrs_tpu/x.py": src})
    assert run_passes(ctx, families=("race",)) == []


# ------------------------------------------------------------ tracing pass

def test_tracing_python_branch_on_traced():
    src = '''
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/ops/x.py": src}))
    assert "tracing.python-branch-on-traced" in rules(findings)


def test_tracing_static_uses_not_flagged():
    src = '''
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("block",))
def f(x, scale, block):
    b, s = x.shape
    if scale is None:          # is-None test: static
        scale = jnp.ones((b,))
    if b > 8:                  # shape-derived: static
        x = x[:8]
    if block > 128:            # static argname
        x = x * 2
    return x * scale
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/ops/x.py": src}))
    assert rules(findings) == set()


def test_tracing_host_sync_and_dynamic_shape():
    src = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def f(x, n):
    v = float(x)               # host sync
    arr = np.asarray(x)        # host sync
    z = jnp.zeros((n, 4))      # traced shape
    for i in range(n):         # traced trip count
        z = z + 1
    return v, arr, z
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/ops/x.py": src}))
    assert rules(findings) >= {"tracing.host-sync-in-jit",
                               "tracing.dynamic-shape-in-jit"}


def test_tracing_lax_scan_body_covered():
    src = '''
from jax import lax

def run(xs):
    def body(carry, x):
        if x > 0:
            carry = carry + x
        return carry, x
    return lax.scan(body, 0, xs)
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/engine/x.py": src}))
    assert "tracing.python-branch-on-traced" in rules(findings)
    assert any("scan-traced" in f.message for f in findings)


def test_tracing_mutable_global_closure():
    src = '''
import jax

_STATE = {"n": 0}

def bump():
    global _STATE
    _STATE = {"n": 1}

@jax.jit
def f(x):
    return x + _STATE["n"]
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/models/x.py": src}))
    assert "tracing.jit-closes-over-mutable-global" in rules(findings)


def test_tracing_deprecated_api_table():
    src = '''
import jax

def f(g, mesh, specs):
    return jax.shard_map(g, mesh=mesh, in_specs=specs, out_specs=specs)
'''
    findings = tracing.run(ctx_for({"lmrs_tpu/serving/x.py": src}))
    dep = [f for f in findings if f.rule == "tracing.deprecated-api"]
    assert dep and "jax_compat" in dep[0].hint


def test_tracing_compat_shim_module_exempt():
    real = (REPO_ROOT / "lmrs_tpu/utils/jax_compat.py").read_text(
        encoding="utf-8")
    findings = tracing.run(ctx_for({"lmrs_tpu/utils/jax_compat.py": real}))
    assert [f for f in findings if f.rule == "tracing.deprecated-api"] == []


# -------------------------------------------------------------- drift pass

DOC_SITES = """
| site | fires as | exercises |
|---|---|---|
| `kv.allocate` | OutOfPages | back-pressure |
| `ghost.site` | nothing | stale row |
"""

DRIFT_SRC = '''
from lmrs_tpu.testing import faults

def step():
    faults.fire("kv.allocate")
    faults.fire("scheduler.newsite")
'''


def test_drift_fault_sites_both_directions():
    ctx = ctx_for({"lmrs_tpu/x.py": DRIFT_SRC},
                  docs={"docs/ROBUSTNESS.md": DOC_SITES,
                        "docs/OBSERVABILITY.md": "", "docs/KNOBS.md": ""})
    findings = drift.run(ctx)
    assert "drift.fault-site-undocumented" in rules(findings)
    assert "drift.fault-site-stale" in rules(findings)
    messages = " ".join(f.message for f in findings)
    assert "scheduler.newsite" in messages and "ghost.site" in messages


METRIC_SRC = '''
class S:
    def __init__(self, registry):
        c, g, h = (registry.counter, registry.gauge, registry.histogram)
        self._c = c("lmrs_widgets_total", "widgets")
        self._g = registry.gauge("lmrs_live_widgets", "live")
'''

METRIC_DOC = """
### Catalog

| metric | type |
|---|---|
| `lmrs_widgets_total` | counter |
| `lmrs_gone_metric` | counter |
"""


def test_drift_metrics_alias_resolution_and_both_directions():
    ctx = ctx_for({"lmrs_tpu/x.py": METRIC_SRC},
                  docs={"docs/OBSERVABILITY.md": METRIC_DOC,
                        "docs/ROBUSTNESS.md": "", "docs/KNOBS.md": ""})
    findings = drift.run(ctx)
    msgs = {f.rule: f.message for f in findings}
    assert "lmrs_live_widgets" in msgs["drift.metric-undocumented"]
    assert "lmrs_gone_metric" in msgs["drift.metric-stale"]


def test_drift_suffix_shorthand_flagged():
    doc = "| `lmrs_widgets_total` / `_live` | counter |\n"
    ctx = ctx_for({}, docs={"docs/OBSERVABILITY.md": doc,
                            "docs/ROBUSTNESS.md": "", "docs/KNOBS.md": ""})
    findings = drift.run(ctx)
    assert "drift.metric-suffix-shorthand" in rules(findings)


def test_drift_trace_instant_args_contract():
    src = '''
def emit(tr, pages, kv_len):
    tr.instant("handoff_export", args={"pages": pages})
    tr.instant("handoff_import", args={"pages": pages, "kv_len": kv_len})
    tr.instant("job_done")
'''
    ctx = ctx_for({"lmrs_tpu/x.py": src},
                  docs={"docs/ROBUSTNESS.md": "",
                        "docs/OBSERVABILITY.md": "", "docs/KNOBS.md": ""})
    findings = [f for f in drift.run(ctx)
                if f.rule == "drift.trace-instant-args"]
    assert len(findings) == 2  # missing kv_len + missing args entirely
    assert any("kv_len" in f.message for f in findings)


# ---------------------------------------------------------------- env pass

def test_env_direct_read_flagged_and_parser_reads_tracked():
    src = '''
import os
from lmrs_tpu.utils.env import env_int

BAD = os.environ.get("LMRS_BAD_KNOB", "1")
GOOD = env_int("LMRS_GOOD_KNOB", 4)
'''
    doc = "| `LMRS_GOOD_KNOB` | 4 | a knob |\n| `LMRS_GONE` | - | stale |\n"
    ctx = ctx_for({"lmrs_tpu/x.py": src}, docs={"docs/KNOBS.md": doc})
    findings = envpass.run(ctx)
    assert "env.direct-read" in rules(findings)
    undocumented = [f for f in findings
                    if f.rule == "env.knob-undocumented"]
    assert ["LMRS_BAD_KNOB" in f.message for f in undocumented] == [True]
    assert any(f.rule == "env.knob-stale" and "LMRS_GONE" in f.message
               for f in findings)


def test_env_module_itself_exempt():
    real = (REPO_ROOT / "lmrs_tpu/utils/env.py").read_text(encoding="utf-8")
    ctx = ctx_for({"lmrs_tpu/utils/env.py": real},
                  docs={"docs/KNOBS.md": ""})
    assert [f for f in envpass.run(ctx)
            if f.rule == "env.direct-read"] == []


# --------------------------------------------------------- golden rendering

def test_golden_finding_output():
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": RACE_POSITIVE}))
    got = "\n".join(f.render() for f in findings)
    want = """\
lmrs_tpu/x.py:11: [race.unguarded-write] assignment to Pool._pinned outside `with _lock:`
    hint: guarded-by declared at line 7; hold _lock for the write, or mark the enclosing function `# holds-lock: _lock` if every caller already holds it
lmrs_tpu/x.py:14: [race.unguarded-write] read-modify-write (+=) to Pool.count outside `with _lock:`
    hint: guarded-by declared at line 8; hold _lock for the write, or mark the enclosing function `# holds-lock: _lock` if every caller already holds it
lmrs_tpu/x.py:17: [race.unguarded-write] .pop() mutation to Pool._pinned outside `with _lock:`
    hint: guarded-by declared at line 7; hold _lock for the write, or mark the enclosing function `# holds-lock: _lock` if every caller already holds it"""
    assert got == want


# ----------------------------------------------------------- baseline file

def test_baseline_accepts_counts_and_expires(tmp_path):
    findings = locks.run(ctx_for({"lmrs_tpu/x.py": RACE_POSITIVE}))
    assert len(findings) == 3
    path = tmp_path / "baseline.json"
    Baseline.from_findings(findings).save(path)

    # same findings -> all accepted, none new, none expired
    new, accepted, expired = Baseline.load(path).apply(findings)
    assert (len(new), len(accepted), expired) == (0, 3, [])

    # one fixed -> its key expires; the rest stay accepted
    new, accepted, expired = Baseline.load(path).apply(findings[:2])
    assert (len(new), len(accepted)) == (0, 2)
    assert len(expired) == 1 and "race.unguarded-write" in expired[0]

    # a NEW duplicate of an accepted key exceeds its count -> new
    new, accepted, expired = Baseline.load(path).apply(
        findings + [findings[0]])
    assert len(new) == 1 and len(accepted) == 3

    # schema is versioned
    doc = json.loads(path.read_text())
    assert doc["schema"] == "lmrs-lint-baseline-v1"
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text('{"schema": "nope", "findings": {}}')
        Baseline.load(bad)


def test_baseline_keys_survive_line_shifts():
    f1 = locks.run(ctx_for({"lmrs_tpu/x.py": RACE_POSITIVE}))
    shifted = "\n\n\n" + RACE_POSITIVE
    f2 = locks.run(ctx_for({"lmrs_tpu/x.py": shifted}))
    assert [f.key for f in f1] != [] and \
        [f.key for f in f1] == [f.key for f in f2]
    assert [f.line for f in f1] != [f.line for f in f2]


def test_write_baseline_refuses_family_subset_runs():
    """--write-baseline from a --family subset would overwrite the whole
    baseline, silently discarding the families that did not run."""
    from lmrs_tpu.analysis.cli import main

    rc = main(["--family", "race", "--write-baseline",
               str(REPO_ROOT)])
    assert rc == 2
    # the checked-in baseline must be untouched (still valid + loadable)
    Baseline.load(REPO_ROOT / "lint-baseline.json")


# --------------------------------------------------------- repo-clean gate

def test_repo_is_lint_clean_against_checked_in_baseline():
    """The CI contract: the tree as committed has no NEW findings."""
    new, _accepted, expired = run_repo(REPO_ROOT)
    assert new == [], "\n" + "\n".join(f.render() for f in new)
    assert expired == [], f"prune expired baseline entries: {expired}"


# ----------------------------------------------- regression: fixed races

def test_tracer_recorded_counts_exactly_under_concurrency():
    """Tracer.recorded was a bare += from concurrent recorder threads —
    lost updates under load.  It now counts under the trace lock."""
    from lmrs_tpu.obs.trace import Tracer

    tr = Tracer(capacity=64)  # tiny ring: drops must not affect recorded
    threads, per = 8, 500

    def hammer():
        for i in range(per):
            tr.instant("spam", tid=1)

    ts = [threading.Thread(target=hammer) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert tr.recorded == threads * per


def test_router_host_counters_count_exactly_under_concurrency():
    """_Host.served/_Host.failed were bare += from dispatch-pool threads
    (one per in-flight request) — the PR 6 lost-update class, now routed
    through the per-host lock."""
    from lmrs_tpu.serving.router import _Host

    host = _Host("127.0.0.1:1")
    threads, per = 8, 500

    def hammer():
        for _ in range(per):
            host.note_served()
            host.note_failed()

    ts = [threading.Thread(target=hammer) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert host.served == threads * per
    assert host.failed == threads * per


# ------------------------------------------- regression: env parser bugs

def test_env_parser_empty_and_nonfinite_fall_back(monkeypatch):
    """The LMRS_POSTMORTEM_MIN_S=\"\" and NaN-duration bug class: empty
    means default, non-finite numbers never escape."""
    from lmrs_tpu.utils import env

    monkeypatch.setenv("LMRS_T_EMPTY", "")
    assert env.env_float("LMRS_T_EMPTY", 5.0) == 5.0
    assert env.env_int("LMRS_T_EMPTY", 7) == 7
    assert env.env_str("LMRS_T_EMPTY", "dflt") == "dflt"

    for bad in ("nan", "inf", "-inf", "NaN"):
        monkeypatch.setenv("LMRS_T_NUM", bad)
        assert env.env_float("LMRS_T_NUM", 5.0) == 5.0

    monkeypatch.setenv("LMRS_T_BOOL", "false")
    assert env.env_bool("LMRS_T_BOOL", True) is False
    monkeypatch.setenv("LMRS_T_BOOL", "banana")
    assert env.env_bool("LMRS_T_BOOL", True) is True

    monkeypatch.setenv("LMRS_T_CLAMP", "2")
    assert env.env_int("LMRS_T_CLAMP", 8, lo=4) == 4


def test_postmortem_throttle_survives_nan(monkeypatch):
    """A NaN LMRS_POSTMORTEM_MIN_S used to win every max() comparison's
    false branch and disable throttling (dump storm); the shared parser
    keeps the documented 5 s default."""
    from lmrs_tpu.obs import flight

    monkeypatch.setenv("LMRS_POSTMORTEM_MIN_S", "nan")
    assert flight._min_interval_s() == 5.0
    monkeypatch.setenv("LMRS_POSTMORTEM_MIN_S", "")
    assert flight._min_interval_s() == 5.0


def test_flash_block_empty_string_does_not_crash(monkeypatch):
    """LMRS_FLASH_BLOCK=\"\" used to raise ValueError at module import
    (int(\"\") at module scope); the parser folds it to the default."""
    from lmrs_tpu.utils.env import env_int

    monkeypatch.setenv("LMRS_FLASH_BLOCK", "")
    assert env_int("LMRS_FLASH_BLOCK", 1024, lo=128) == 1024


# ------------------------------------------------------------ shim smoke

def test_jax_compat_shard_map_resolves():
    """The compat shim must resolve on the installed jax."""
    from lmrs_tpu.utils.jax_compat import shard_map, tpu_compiler_params

    assert callable(shard_map)
    params = tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert params is not None
