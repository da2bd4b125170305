"""Static-analysis framework tests: per-rule firing + clean fixtures,
the suppression roundtrip, the knob registry/docs sync, repo-wide lint
cleanliness, and the two runtime sanitizers (recompile + transfer) over
steady-state batched decode.

Fixture snippets are compiled through SourceFile with VIRTUAL paths so a
snippet can be placed on (or off) the hot-path module set without
touching real files.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu import knobs
from cake_tpu.analysis import RULES, SourceFile, check_file, run_paths
from cake_tpu.analysis.sanitizers import (RecompileError,
                                          assert_no_recompiles,
                                          no_implicit_transfers)

HOT = "cake_tpu/serve/engine.py"        # virtual: on the hot-path set
COLD = "cake_tpu/tui.py"                # virtual: off it


def fire(src: str, rule: str, rel: str = HOT):
    sf = SourceFile(rel, src)
    return [v for v in check_file(sf, [rule]) if v.rule == rule]


# -- host-sync ------------------------------------------------------------

HOST_SYNC_FIRING = """
import numpy as np

def fanout(model, layers, toks):
    packed = model.decode_slots(layers, toks)
    vals = np.asarray(packed)
    return vals

def peek(model, layers, toks):
    packed, layers = model.decode_slots(layers, toks)
    return int(packed)

def item_read(x):
    return x.item()
"""

HOST_SYNC_CLEAN = """
import numpy as np

def host_only(ids):
    arr = np.asarray(list(ids), np.int32)
    return int(arr[0]) + float(arr[1])
"""


def test_host_sync_fires():
    got = fire(HOST_SYNC_FIRING, "host-sync")
    msgs = " | ".join(v.msg for v in got)
    assert len(got) == 3
    assert "np.asarray(packed)" in msgs
    assert "int(packed)" in msgs
    assert ".item()" in msgs


def test_host_sync_clean_on_host_data():
    assert fire(HOST_SYNC_CLEAN, "host-sync") == []


def test_host_sync_scoped_to_hot_paths():
    assert fire(HOST_SYNC_FIRING, "host-sync", rel=COLD) == []


def test_host_sync_tracer_truthiness():
    src = """
import functools, jax

@functools.partial(jax.jit, static_argnames=("n",))
def step(x, flag, n):
    if flag:
        return x + n
    return x
"""
    got = fire(src, "host-sync")
    assert len(got) == 1 and "truthiness" in got[0].msg
    clean = src.replace("if flag:", "if n > 2:")
    assert fire(clean, "host-sync") == []


# -- recompile-hazard -----------------------------------------------------

RECOMPILE_FIRING = """
import functools, jax

@functools.partial(jax.jit, static_argnames=("tag", "scale"))
def step(x, tag, scale):
    return x

def caller(x, i):
    return step(x, f"req-{i}", 0.5)
"""


def test_recompile_unstable_static_args():
    got = fire(RECOMPILE_FIRING, "recompile-hazard")
    assert len(got) == 2
    assert any("f-string" in v.msg for v in got)
    assert any("float literal" in v.msg for v in got)
    clean = RECOMPILE_FIRING.replace('f"req-{i}", 0.5', '"decode", 2')
    assert fire(clean, "recompile-hazard") == []


def test_recompile_shape_branch():
    src = """
import functools, jax

@functools.partial(jax.jit, static_argnames=("nb",))
def step(x, y, nb):
    if nb == x.shape[0]:
        return x
    return y
"""
    got = fire(src, "recompile-hazard")
    assert len(got) == 1 and "x.shape" in got[0].msg
    # branching on the STATIC arg alone is stable
    clean = src.replace("if nb == x.shape[0]:", "if nb == 4:")
    assert fire(clean, "recompile-hazard") == []


# -- use-after-donate -----------------------------------------------------

DONATE_FIRING = """
import functools, jax

@functools.partial(jax.jit, donate_argnums=(1,))
def step(params, cache, tok):
    return tok, cache

def loop(params, cache, tok):
    tok, new_cache = step(params, cache, tok)
    return cache["layers"]
"""

DONATE_CLEAN = DONATE_FIRING.replace(
    "tok, new_cache = step(params, cache, tok)\n    return cache",
    "tok, cache = step(params, cache, tok)\n    return cache")


def test_donation_fires_and_rebind_clears():
    got = fire(DONATE_FIRING, "use-after-donate")
    assert len(got) == 1 and "'cache'" in got[0].msg
    assert fire(DONATE_CLEAN, "use-after-donate") == []


def test_donation_known_method_and_self_attr():
    src = """
def release(self, slot):
    out = self.model.slot_release(self._layers, slot)
    return self._layers
"""
    got = fire(src, "use-after-donate")
    assert len(got) == 1 and "self._layers" in got[0].msg
    clean = src.replace("out =", "self._layers =")
    assert fire(clean, "use-after-donate") == []


# -- knob-registry --------------------------------------------------------

def test_knob_rule_fires_on_raw_reads():
    src = """
import os

def f():
    a = os.environ.get("CAKE_SERVE_SLOTS", "4")
    b = os.getenv("CAKE_MAX_QUEUE")
    c = os.environ["CAKE_SERVE_CTX"]
    return a, b, c
"""
    got = fire(src, "knob-registry")
    assert len(got) == 3


def test_knob_rule_allows_writes_and_non_cake():
    src = """
import os

def f():
    os.environ["CAKE_SERVE_SLOTS"] = "2"
    os.environ.setdefault("CAKE_MAX_QUEUE", "8")
    return os.environ.get("JAX_PLATFORMS")
"""
    assert fire(src, "knob-registry") == []


def test_knob_rule_exempts_registry_module():
    src = 'import os\nX = os.environ.get("CAKE_SERVE_SLOTS")\n'
    assert fire(src, "knob-registry", rel="cake_tpu/knobs.py") == []
    assert len(fire(src, "knob-registry", rel=COLD)) == 1


# -- metric-registry ------------------------------------------------------

def test_metric_rule_fires_on_uncataloged_name():
    src = """
from cake_tpu.obs import REGISTRY

BOGUS = REGISTRY.counter("cake_fixture_bogus_total", "never documented")
"""
    got = fire(src, "metric-registry")
    assert len(got) == 1 and "cake_fixture_bogus_total" in got[0].msg


def test_metric_rule_clean_on_cataloged_and_foreign_names():
    src = """
from cake_tpu.obs import REGISTRY

TTFT = REGISTRY.histogram("cake_ttft_seconds", "documented")
OTHER = REGISTRY.counter("someone_elses_metric_total", "not ours")
H = some.other.histogram([1, 2, 3])
"""
    assert fire(src, "metric-registry") == []


def test_metric_rule_scoped_to_package_and_suppressible():
    src = ('from cake_tpu.obs import REGISTRY\n'
           'X = REGISTRY.gauge("cake_fixture_bogus")\n')
    assert fire(src, "metric-registry", rel="scripts/foo.py") == []
    sup = ('from cake_tpu.obs import REGISTRY\n'
           'X = REGISTRY.gauge("cake_fixture_bogus")'
           '  # lint: disable=metric-registry — fixture\n')
    got = fire(sup, "metric-registry")
    assert len(got) == 1 and got[0].suppressed


def test_metric_rule_slo_bucket_mismatch_fires():
    """SLO-semantic (ttft/itl/e2e *_seconds) histograms must share the
    LATENCY_BUCKETS boundaries — the fleet telemetry plane sums their
    buckets across replicas, and mismatched edges make the merged
    percentiles silently wrong."""
    src = """
from cake_tpu.obs import REGISTRY
from cake_tpu.obs.metrics import LATENCY_BUCKETS

A = REGISTRY.histogram("cake_serve_ttft_seconds", "doc", ("outcome",))
B = REGISTRY.histogram("cake_serve_ttft_seconds", "doc", ("outcome",),
                       buckets=(0.1, 0.5, 1.0))
"""
    got = fire(src, "metric-registry")
    assert len(got) == 2
    assert any("!= the shared LATENCY_BUCKETS" in v.msg for v in got)
    # the same-file same-semantic check names the declaration it differs
    # from
    assert any("line 5" in v.msg for v in got)


def test_metric_rule_slo_buckets_clean_forms():
    """Omitted buckets, the LATENCY_BUCKETS name, and the
    attribute-qualified form all mean 'the canonical boundaries'."""
    src = """
from cake_tpu.obs import REGISTRY, metrics
from cake_tpu.obs.metrics import LATENCY_BUCKETS

A = REGISTRY.histogram("cake_serve_ttft_seconds", "doc", ("outcome",))
B = REGISTRY.histogram("cake_serve_itl_seconds", "doc", ("outcome",),
                       buckets=LATENCY_BUCKETS)
C = REGISTRY.histogram("cake_serve_e2e_seconds", "doc", ("outcome",),
                       buckets=metrics.LATENCY_BUCKETS)
"""
    assert fire(src, "metric-registry") == []


def test_metric_rule_slo_unverifiable_buckets_fire():
    src = """
from cake_tpu.obs import REGISTRY

def mk(edges):
    return REGISTRY.histogram("cake_serve_e2e_seconds", "doc",
                              ("outcome",), buckets=edges)
"""
    got = fire(src, "metric-registry")
    assert len(got) == 1 and "cannot verify statically" in got[0].msg


def test_metric_rule_non_slo_histograms_unconstrained():
    src = """
from cake_tpu.obs import REGISTRY

H = REGISTRY.histogram("cake_api_request_seconds", "doc", ("endpoint",),
                       buckets=(0.1, 0.5, 1.0))
"""
    assert fire(src, "metric-registry") == []


def test_observability_doc_generated_and_in_sync():
    """docs/observability.md is GENERATED (metric table from the obs
    registry, span table from SPAN_CATALOG, event table from
    EVENT_KINDS); regenerate with `make metrics-doc` if this fails —
    the metric-registry lint checks instrument names against it."""
    from cake_tpu.obs.catalog import generate_doc
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "observability.md")
    with open(path, encoding="utf-8") as f:
        assert f.read().rstrip() == generate_doc().rstrip(), \
            "docs/observability.md is stale — run `make metrics-doc`"


def test_catalog_covers_every_registered_instrument():
    """Every instrument in the live registry appears in the catalog the
    lint checks against — the invariant that makes 'lint passes' mean
    'nothing undocumented'."""
    from cake_tpu import obs
    from cake_tpu.analysis.check_metrics import catalog_names
    names = catalog_names()
    missing = [m for m in obs.REGISTRY._metrics if m not in names]
    assert not missing, f"catalog missing {missing} — run `make metrics-doc`"


# -- lock-discipline ------------------------------------------------------

LOCKS_SRC = """
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._cbs = []          # guarded-by: self._lock

    def good(self, cb):
        with self._lock:
            self._cbs.append(cb)

    def bad(self):
        return list(self._cbs)
"""


def test_lock_discipline():
    got = fire(LOCKS_SRC, "lock-discipline")
    assert len(got) == 1 and "self._cbs" in got[0].msg
    clean = LOCKS_SRC.replace(
        "        return list(self._cbs)",
        "        with self._lock:\n            return list(self._cbs)")
    assert fire(clean, "lock-discipline") == []


def test_lock_discipline_wrong_lock_does_not_count():
    src = LOCKS_SRC.replace(
        "        return list(self._cbs)",
        "        with self._other:\n            return list(self._cbs)")
    assert len(fire(src, "lock-discipline")) == 1


# -- hot-timing -----------------------------------------------------------

def test_hot_timing():
    src = "import time\n\ndef f():\n    return time.monotonic()\n"
    got = fire(src, "hot-timing")
    assert len(got) == 1 and "time.monotonic" in got[0].msg
    assert fire(src, "hot-timing", rel=COLD) == []       # not hot
    ok = "import time\n\ndef f():\n    time.sleep(0.1)\n"
    assert fire(ok, "hot-timing") == []                  # sleep is legal


# -- suppressions ---------------------------------------------------------

def test_suppression_roundtrip_inline_and_standalone():
    inline = ("import time\n\ndef f():\n"
              "    return time.monotonic()  "
              "# lint: disable=hot-timing — bench-only helper\n")
    got = fire(inline, "hot-timing")
    assert len(got) == 1 and got[0].suppressed
    assert got[0].reason == "bench-only helper"

    standalone = ("import time\n\ndef f():\n"
                  "    # lint: disable=hot-timing — bench-only helper\n"
                  "    return time.monotonic()\n")
    got = fire(standalone, "hot-timing")
    assert len(got) == 1 and got[0].suppressed

    wrong_rule = inline.replace("hot-timing —", "host-sync —")
    got = fire(wrong_rule, "hot-timing")
    assert len(got) == 1 and not got[0].suppressed


def test_suppression_without_reason_is_a_violation():
    src = ("import time\n\ndef f():\n"
           "    return time.monotonic()  # lint: disable=hot-timing\n")
    sf = SourceFile(HOT, src)
    out = check_file(sf, ["hot-timing"])
    rules = {v.rule for v in out}
    assert "suppression-format" in rules
    # and the underlying violation is NOT suppressed
    assert any(v.rule == "hot-timing" and not v.suppressed for v in out)


# -- registry / repo-wide -------------------------------------------------

def test_all_rules_registered():
    assert set(RULES) == {"host-sync", "recompile-hazard",
                          "use-after-donate", "knob-registry",
                          "lock-discipline", "hot-timing",
                          "metric-registry"}


def test_repo_is_lint_clean():
    """`make lint` in-process: no unsuppressed violations anywhere, and
    every suppression carries a reason (format errors are violations)."""
    bad = [v.render() for v in run_paths() if not v.suppressed]
    assert not bad, "lint violations:\n" + "\n".join(bad)


def test_guarded_by_annotations_present():
    """The lock-discipline rule only has teeth while the annotations
    exist — pin the ones this PR established."""
    from cake_tpu.analysis.check_locks import LockDisciplineChecker
    import ast
    c = LockDisciplineChecker()
    found = {}
    for rel in ("cake_tpu/serve/engine.py", "cake_tpu/cluster/master.py"):
        path = os.path.join(os.path.dirname(__file__), "..", rel)
        sf = SourceFile(rel, open(path).read())
        for cls in ast.walk(sf.tree):
            if isinstance(cls, ast.ClassDef):
                found.update({f"{cls.name}.{k}": v for k, v in
                              c._guarded_fields(sf, cls).items()})
    assert found.get("ServeRequest._token_cb") == "self._sub_lock"
    assert found.get("ServeRequest._done_cbs") == "self._sub_lock"
    assert found.get("DistributedTextModel.degraded") == \
        "self._degraded_lock"


# -- knob registry --------------------------------------------------------

def test_knobs_typed_get_and_empty_fallback(monkeypatch):
    monkeypatch.setenv("CAKE_SERVE_SLOTS", "7")
    assert knobs.get("CAKE_SERVE_SLOTS") == 7
    monkeypatch.setenv("CAKE_SERVE_SLOTS", "")
    assert knobs.get("CAKE_SERVE_SLOTS") == 4       # empty == unset
    monkeypatch.setenv("CAKE_TPU_FLASH", "0")
    assert knobs.get("CAKE_TPU_FLASH") is False
    monkeypatch.delenv("CAKE_SPEC", raising=False)
    assert knobs.get("CAKE_SPEC") is None
    assert knobs.get_str("CAKE_SPEC") == ""
    with pytest.raises(KeyError):
        knobs.get("CAKE_NOT_A_KNOB")


def test_knobs_doc_generated_and_in_sync():
    """docs/knobs.md is GENERATED from the registry; regenerate with
    `make knobs-doc` if this fails."""
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "knobs.md")
    want = knobs.generate_doc().rstrip()
    with open(path, encoding="utf-8") as f:
        assert f.read().rstrip() == want, \
            "docs/knobs.md is stale — run `make knobs-doc`"


def test_every_knob_documented_and_typed():
    for kb in knobs.REGISTRY.values():
        assert kb.name.startswith("CAKE_")
        assert kb.cast in (int, float, str, bool)
        assert len(kb.doc) > 10, kb.name
        if kb.default is not None:
            assert isinstance(kb.default, kb.cast), kb.name


# -- runtime sanitizers ---------------------------------------------------

SLOTS = 2


@pytest.fixture(scope="module")
def tiny_model():
    from cake_tpu.models import TextModel, tiny_config
    return TextModel(tiny_config("llama"), dtype=jnp.float32,
                     max_cache_len=64)


def make_state(m, slots=SLOTS):
    """A warmed pool (2 slots unless told otherwise) mid-decode (the
    steady state the sanitizers must hold over). Fresh per test: the
    negative tests donate or kill buffers, so shared mutable state would
    leak between tests."""
    layers = m.new_cache(slots, kv_len=64)["layers"]
    for s in range(slots):
        _, layers = m.prefill_chunk(layers, s, [1, 2, 3], 0)
    return {
        "layers": layers,
        "toks": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.full((slots,), 3, jnp.int32),
        "rngs": jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
        "recents": jnp.full((slots, 64), -1, jnp.int32),
        "temps": jnp.zeros((slots,), jnp.float32),
        "top_ks": jnp.full((slots,), m.cfg.vocab_size, jnp.int32),
        "top_ps": jnp.ones((slots,), jnp.float32),
        "pens": jnp.ones((slots,), jnp.float32),
        "act": jnp.ones((slots,), jnp.bool_),
    }


def _step(m, st, toks=None):
    (packed, st["layers"], st["toks"], st["pos"], st["rngs"],
     st["recents"]) = m.decode_slots(
        st["layers"], st["toks"] if toks is None else toks, st["pos"],
        st["rngs"], st["recents"], st["temps"], st["top_ks"],
        st["top_ps"], st["pens"], st["act"])
    return packed


def test_steady_state_decode_zero_recompiles_no_transfers(tiny_model):
    """The acceptance bar: >= 8 consecutive steady-state decode_slots
    iterations compile zero new executables, and the step itself performs
    no implicit device<->host transfers (the one planned fetch happens
    outside the guard)."""
    m = tiny_model
    st = make_state(m)
    _step(m, st)                            # warm the one program
    with assert_no_recompiles(m, label="decode_slots steady state"):
        for _ in range(8):
            with no_implicit_transfers():
                packed = _step(m, st)
            ids = np.asarray(packed)        # planned fetch, outside guard
    assert ids.shape == (2, SLOTS)


def test_recompile_sanitizer_catches_new_bucket(tiny_model):
    """Occupancy compiles nothing (one program per pool shape), so the
    new program comes from a pool of another size."""
    m = tiny_model
    st = make_state(m)
    _step(m, st)
    wider = make_state(m, slots=SLOTS + 1)
    with pytest.raises(RecompileError, match="_decode_slots"):
        with assert_no_recompiles(m):
            _step(m, wider)                 # unwarmed pool shape: new program


def test_transfer_sanitizer_catches_implicit_host_to_device(tiny_model):
    m = tiny_model
    st = make_state(m)
    _step(m, st)
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with no_implicit_transfers():
            # a host numpy array smuggled into the traced step is exactly
            # the implicit per-iteration upload the guard exists to catch
            _step(m, st, toks=np.zeros((SLOTS,), np.int32))
