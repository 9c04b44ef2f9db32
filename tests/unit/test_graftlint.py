"""graftlint fixture corpus: one minimal repro per rule, suppression behavior,
the JSON report schema, and CLI exit codes.

These pins are the linter's own regression suite — the companion
``test_lint_clean.py`` is the CI gate that holds the *shipped tree* finding-free.
Fixtures are written to ``tmp_path`` so each repro is a real file run through
the full pipeline (tokenize comments + ast + call graph), not a unit poke at a
rule function.
"""

import json

import pytest

from unionml_tpu.analysis import REPORT_VERSION, run_lint
from unionml_tpu.analysis.__main__ import main as lint_main

# --------------------------------------------------------------------- corpus

HOST_SYNC_REPRO = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def traced(x):
    return np.asarray(x) + x.sum().item()

def fetch_helper(x):
    return x.block_until_ready()

def steady(x):  # graftlint: hot-path
    return fetch_helper(jax.device_get(x))
'''

RETRACE_REPRO = '''
import jax

def f(x, k):
    return x * k

g = jax.jit(f, static_argnums=(1,))

def sites(x):
    return g(x, 2), g(x, 3), g([1, 2], 4)

def churn(xs):
    for x in xs:
        h = jax.jit(lambda v: v + 1)
    return h
'''

SHARDING_REPRO = '''
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make(devs):
    return Mesh(np.asarray(devs), ("data", "tensor"))

def layout(mesh, stray):
    return NamedSharding(mesh, P("tensr")), NamedSharding(stray, P("data"))
'''

LOCKS_REPRO = '''
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._queue = []  # guarded-by: _lock
        # guarded-by: _lock
        self.stats = object()

    def enqueue(self, item):
        self._queue.append(item)          # BAD: no lock held

    def bump(self, n):
        self.stats.count = n              # BAD: nested write, no lock held
        with self._lock:
            self._queue.append(n)         # ok
'''

SUPPRESSED = '''
import jax

@jax.jit
def traced(x):
    # graftlint: disable=host-sync -- fixture: documents a known-safe concretization
    return x.sum().item()
'''

CLEAN = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return jnp.where(x > 0, x, -x)

def drive(x):  # graftlint: hot-path
    return step(x)
'''


def _lint_source(tmp_path, name, source, rules=None):
    f = tmp_path / f"{name}.py"
    f.write_text(source)
    return run_lint([str(f)], rules)


# ------------------------------------------------------------- per-rule repros


def test_host_sync_repro_fires_and_reaches_through_the_call_graph(tmp_path):
    result = _lint_source(tmp_path, "hs", HOST_SYNC_REPRO)
    rules = {f.rule for f in result.findings}
    assert rules == {"host-sync"}
    messages = "\n".join(f.message for f in result.findings)
    assert "np.asarray" in messages and ".item()" in messages
    # call-graph, not syntax: the hazard inside fetch_helper is attributed
    # because the hot-path root `steady` calls it
    assert any(f.symbol == "fetch_helper" for f in result.findings)
    assert any(f.symbol == "steady" for f in result.findings)


def test_retrace_repro_fires(tmp_path):
    result = _lint_source(tmp_path, "rt", RETRACE_REPRO)
    assert {f.rule for f in result.findings} == {"retrace"}
    messages = "\n".join(f.message for f in result.findings)
    assert "distinct literal values" in messages        # static ladder variance
    assert "container literal" in messages              # [1, 2] in traced position
    assert "inside a loop" in messages                  # jit-in-loop


def test_sharding_repro_fires(tmp_path):
    result = _lint_source(tmp_path, "sh", SHARDING_REPRO)
    assert {f.rule for f in result.findings} == {"sharding"}
    messages = "\n".join(f.message for f in result.findings)
    assert "'tensr'" in messages                        # unknown axis
    assert "'stray'" in messages                        # foreign mesh variable


def test_lock_discipline_repro_fires(tmp_path):
    result = _lint_source(tmp_path, "lk", LOCKS_REPRO)
    assert {f.rule for f in result.findings} == {"lock-discipline"}
    assert len(result.findings) == 2  # append outside lock + nested stats write
    lines = {f.line for f in result.findings}
    symbols = {f.symbol for f in result.findings}
    assert symbols == {"Worker.enqueue", "Worker.bump"}
    # the locked append is NOT flagged
    assert max(lines) < LOCKS_REPRO.count("\n")


def test_clean_fixture_is_finding_free(tmp_path):
    result = _lint_source(tmp_path, "ok", CLEAN)
    assert result.ok, [f.format() for f in result.findings]
    assert not result.suppressed


SWALLOWED_REPRO = '''
def silent_pass():
    try:
        work()
    except Exception:
        pass

def silent_bare():
    try:
        work()
    except:
        return None

def silent_sentinel():
    try:
        return probe()
    except Exception:
        return False
'''

SWALLOWED_CLEAN = '''
import logging
logger = logging.getLogger(__name__)

def reraises():
    try:
        work()
    except Exception:
        raise

def wraps():
    try:
        work()
    except Exception as exc:
        raise RuntimeError(f"work failed: {exc}")

def logs():
    try:
        work()
    except Exception:
        logger.exception("work failed")

def records(sink):
    try:
        work()
    except Exception as exc:
        sink.fail(exc)

def narrow_is_deliberate():
    try:
        return int(probe())
    except (ValueError, TypeError):
        return 0
'''


def test_swallowed_exception_repro_fires(tmp_path):
    result = _lint_source(tmp_path, "sw", SWALLOWED_REPRO)
    assert {f.rule for f in result.findings} == {"swallowed-exception"}
    assert len(result.findings) == 3
    assert {f.symbol for f in result.findings} == {
        "silent_pass", "silent_bare", "silent_sentinel",
    }
    assert any("bare except" in f.message for f in result.findings)


def test_swallowed_exception_accepts_reraise_log_and_record(tmp_path):
    result = _lint_source(tmp_path, "swc", SWALLOWED_CLEAN)
    assert result.ok, [f.format() for f in result.findings]


def test_swallowed_exception_suppression_with_reason(tmp_path):
    source = SWALLOWED_REPRO.replace(
        "    except Exception:\n        pass",
        "    except Exception:  # graftlint: disable=swallowed-exception -- fixture: best-effort probe\n        pass",
    )
    result = _lint_source(tmp_path, "sws", source)
    assert {f.symbol for f in result.findings} == {"silent_bare", "silent_sentinel"}
    assert len(result.suppressed) == 1
    assert result.suppressed[0].rule == "swallowed-exception"


# -------------------------------------------------------------- suppressions


def test_suppression_silences_with_reason_and_is_reported(tmp_path):
    result = _lint_source(tmp_path, "sup", SUPPRESSED)
    assert result.ok, [f.format() for f in result.findings]
    assert len(result.suppressed) == 1
    sup = result.suppressed[0]
    assert sup.rule == "host-sync" and sup.suppressed
    assert sup.reason == "fixture: documents a known-safe concretization"


def test_suppression_without_reason_is_itself_a_finding(tmp_path):
    source = SUPPRESSED.replace(" -- fixture: documents a known-safe concretization", "")
    result = _lint_source(tmp_path, "noreason", source)
    rules = {f.rule for f in result.findings}
    # the hazard is NOT silenced and the naked suppression is flagged
    assert rules == {"host-sync", "suppression"}
    assert any("requires a reason" in f.message for f in result.findings)


def test_suppression_of_unknown_rule_is_flagged(tmp_path):
    source = SUPPRESSED.replace("disable=host-sync", "disable=not-a-rule")
    result = _lint_source(tmp_path, "unknown", source)
    assert any(
        f.rule == "suppression" and "unknown rule" in f.message for f in result.findings
    )
    assert any(f.rule == "host-sync" for f in result.findings)  # not silenced


def test_inline_suppression_applies_to_its_own_line(tmp_path):
    source = (
        "import jax\n\n@jax.jit\ndef traced(x):\n"
        "    return x.sum().item()  # graftlint: disable=host-sync -- fixture inline\n"
    )
    result = _lint_source(tmp_path, "inline", source)
    assert result.ok and len(result.suppressed) == 1


# ----------------------------------------------------------------- the report


def test_json_report_schema(tmp_path):
    result = _lint_source(tmp_path, "schema", HOST_SYNC_REPRO)
    report = json.loads(result.report_json())
    assert report["graftlint"] == REPORT_VERSION == 3
    assert set(report) == {
        "graftlint", "paths", "rules", "files", "counts",
        "findings", "suppressed", "baselined", "timings",
    }
    assert report["timings"]["parse"] >= 0.0  # per-family wall, seconds
    assert report["files"] == 1
    assert report["counts"] == {
        "findings": len(result.findings),
        "suppressed": len(result.suppressed),
        "baselined": 0,
    }
    for entry in report["findings"]:
        assert set(entry) == {"rule", "path", "line", "col", "message", "symbol"}
        assert isinstance(entry["line"], int) and entry["line"] > 0
    # suppressed entries carry the reason
    sup = _lint_source(tmp_path, "schema_sup", SUPPRESSED).report()
    assert sup["suppressed"][0]["reason"]


def test_rule_subset_selection(tmp_path):
    result = _lint_source(tmp_path, "subset", HOST_SYNC_REPRO, rules=["sharding"])
    assert result.ok  # the host-sync hazards are out of scope for this run
    with pytest.raises(ValueError, match="unknown rule"):
        _lint_source(tmp_path, "subset2", CLEAN, rules=["nope"])


def test_syntax_error_is_a_parse_finding_not_a_crash(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    result = run_lint([str(f)])
    assert any(fi.rule == "parse" for fi in result.findings)


# ------------------------------------------------------------------------ CLI


def test_cli_exits_nonzero_on_each_rule_repro_and_zero_on_clean(tmp_path, capsys):
    """The acceptance contract: non-zero on every per-rule repro, zero clean."""
    repros = {
        "host-sync": HOST_SYNC_REPRO,
        "retrace": RETRACE_REPRO,
        "sharding": SHARDING_REPRO,
        "lock-discipline": LOCKS_REPRO,
    }
    for rule, source in repros.items():
        bad = tmp_path / f"{rule.replace('-', '_')}_repro.py"
        bad.write_text(source)
        assert lint_main([str(bad)]) == 1, f"{rule} repro did not fail the CLI"
    ok = tmp_path / "ok.py"
    ok.write_text(CLEAN)
    assert lint_main([str(ok)]) == 0
    bad = tmp_path / "host_sync_repro.py"
    assert lint_main([str(bad), "--no-fail-on-findings"]) == 0
    assert lint_main([str(bad), "--rules", "nope"]) == 2
    capsys.readouterr()


def test_cli_writes_json_report(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(RETRACE_REPRO)
    out = tmp_path / "report.json"
    assert lint_main([str(bad), "--json", str(out)]) == 1
    report = json.loads(out.read_text())
    assert report["graftlint"] == REPORT_VERSION
    assert report["counts"]["findings"] > 0
    capsys.readouterr()


# ======================================================================
# v2: interprocedural dataflow rule families (use-after-donate,
# lock-order, async-blocking), suppression anchoring, baseline, SARIF
# ======================================================================

DONATE_REPRO = '''
import jax

def f(state, batch):
    return state, 1.0

step = jax.jit(f, donate_argnums=(0,))

def use_after(state, batch):
    out, loss = step(state, batch)
    return state

def loop_carried(state, batches):
    for b in batches:
        out, loss = step(state, b)
    return out

def disciplined(state, batches):
    for b in batches:
        state, loss = step(state, b)
    return state

class Engine:
    def __init__(self):
        self._pool = jax.numpy.zeros((4,))
        self._save = jax.jit(f, donate_argnums=(0,))

    def leak(self, batch):
        out, loss = self._save(self._pool, batch)
        return out

    def rebind(self, batch):
        self._pool, loss = self._save(self._pool, batch)
'''

FACTORY_DONATE_REPRO = '''
import jax

def make_step():
    def step(state, batch):
        return state, 1.0
    return jax.jit(step, donate_argnums=(0,))

def wrapper_factory():
    return make_step()

def caller(state, batch):
    step = wrapper_factory()
    out, loss = step(state, batch)
    return state
'''

LOCK_ORDER_REPRO = '''
import threading

def fetch(x):
    import jax
    return jax.device_get(x)

class Worker:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()
        self._cv = threading.Condition()

    def ab(self):
        with self._la:
            with self._lb:
                return 1

    def ba(self):
        with self._lb:
            with self._la:
                return 2

    def slow(self, fut):
        with self._la:
            return fut.result()

    def chain(self, x):
        with self._lb:
            return fetch(x)

    def cv_ok(self):
        with self._cv:
            while True:
                self._cv.wait()
'''

ASYNC_REPRO = '''
import asyncio
import time

import jax

class Predictor:
    def predict(self, x):
        return jax.device_get(x)

def build():
    predictor = Predictor()

    async def handler(x):
        return predictor.predict(x)

    async def ok(x):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, lambda: predictor.predict(x))

    return handler, ok

async def sleepy():
    time.sleep(1)
    return 1

async def awaited_ok(q):
    return await q.get()
'''

ALIASED_DEVICE_REPRO = '''
import jax.numpy as jnp

class Engine:
    def __init__(self):
        self._tokens = jnp.zeros((4,))
        self._count = 0

    def step(self):  # graftlint: hot-path
        x = self._tokens
        n = self._count
        return bool(x), int(n)
'''


def test_use_after_donate_repro_fires(tmp_path):
    """TP: linear read-after-donate, loop-carried donation, donated self-attr
    never rebound. TN: the rebinding discipline in `disciplined` / `rebind`."""
    result = _lint_source(tmp_path, "don", DONATE_REPRO)
    assert {f.rule for f in result.findings} == {"use-after-donate"}
    triples = {(f.rule, f.line, f.symbol) for f in result.findings}
    assert triples == {
        ("use-after-donate", 11, "use_after"),
        ("use-after-donate", 15, "loop_carried"),
        ("use-after-donate", 29, "Engine.leak"),
    }
    messages = {f.symbol: f.message for f in result.findings}
    assert "loop's next iteration" in messages["loop_carried"]
    assert "never rebound" in messages["Engine.leak"]


def test_use_after_donate_resolves_factories_across_functions(tmp_path):
    """`step = wrapper_factory()` donates because the factory chain ends in
    jax.jit(..., donate_argnums=(0,)) two calls away."""
    result = _lint_source(tmp_path, "fact", FACTORY_DONATE_REPRO)
    assert [(f.rule, f.line, f.symbol) for f in result.findings] == [
        ("use-after-donate", 15, "caller")
    ]


def test_lock_order_repro_fires(tmp_path):
    """TP: an A->B / B->A acquisition cycle (reported at both sites), a
    blocking .result() under a lock, and an INTERPROCEDURAL device fetch under
    a lock. TN: unbounded Condition.wait on the HELD condition (the cv
    protocol releases it)."""
    result = _lint_source(tmp_path, "lk2", LOCK_ORDER_REPRO)
    assert {f.rule for f in result.findings} == {"lock-order"}
    triples = {(f.line, f.symbol) for f in result.findings}
    assert triples == {
        (16, "Worker.ab"), (21, "Worker.ba"),   # the cycle, once per edge site
        (26, "Worker.slow"),                     # .result() under _la
        (30, "Worker.chain"),                    # device fetch via fetch() under _lb
    }
    messages = "\n".join(f.message for f in result.findings)
    assert "lock-order cycle" in messages
    assert ".result() without a timeout" in messages
    # the interprocedural finding names the chain down to the primitive
    assert "fetch reaches 'jax.device_get()" in messages
    # the cv wait is NOT flagged
    assert not any(f.symbol == "Worker.cv_ok" for f in result.findings)


def test_async_blocking_repro_fires(tmp_path):
    """TP: a direct time.sleep in an async def, and an instance-type-resolved
    chain (predictor = Predictor(); predictor.predict -> jax.device_get). TN:
    run_in_executor lambdas and awaited calls."""
    result = _lint_source(tmp_path, "async", ASYNC_REPRO)
    assert {f.rule for f in result.findings} == {"async-blocking"}
    triples = {(f.line, f.symbol) for f in result.findings}
    assert triples == {(15, "build.handler"), (24, "sleepy")}
    chain = next(f for f in result.findings if f.symbol == "build.handler")
    assert "Predictor.predict" in chain.message and "jax.device_get" in chain.message
    # the executor path and the awaited queue.get are NOT findings
    assert not any(f.symbol in ("build.ok", "awaited_ok") for f in result.findings)


def test_host_sync_catches_aliased_device_value_v1_provably_missed(tmp_path):
    """The dataflow retrofit: `x = self._tokens; bool(x)` is flagged because
    __init__ assigned self._tokens a jnp result. The regression half: no
    identifier in the flagged expression carries the `_dev` suffix, so v1's
    purely syntactic suffix match alone COULD NOT have flagged it."""
    result = _lint_source(tmp_path, "alias", ALIASED_DEVICE_REPRO)
    assert [(f.rule, f.line, f.symbol) for f in result.findings] == [
        ("host-sync", 12, "Engine.step")
    ]
    finding = result.findings[0]
    # v1's predicate: some name in the conversion arg ends with "_dev".
    # The flagged value is the bare alias `x` — v1-invisible by construction.
    assert "value(s) x " in finding.message
    assert not "x".endswith("_dev")
    # the int(n) on the host-side counter is NOT flagged (provenance, not
    # paranoia: _count is a plain int attr)
    assert "int" not in finding.message.split("fetches")[0]


def test_shape_derived_locals_are_not_traced_syncs(tmp_path):
    """`num_tokens, _ = gates.shape` then int(num_tokens * k) inside a traced
    body is trace-time python, not a host sync (the ep.py moe pattern)."""
    source = (
        "import jax\nimport numpy as np\n\n"
        "@jax.jit\n"
        "def traced(gates, k):\n"
        "    num_tokens, num_experts = gates.shape\n"
        "    capacity = max(int(np.ceil(num_tokens * k / num_experts)), 1)\n"
        "    return gates * capacity\n"
    )
    result = _lint_source(tmp_path, "shapes", source)
    assert result.ok, [f.format() for f in result.findings]


# ------------------------------------------------------- golden JSON reports


def test_golden_reports_for_new_rule_families(tmp_path):
    """Full machine-readable pins for the three new families: rule ids, lines,
    columns, symbols — the report shape downstream tooling consumes."""
    golden = {
        "don": [
            {"rule": "use-after-donate", "line": 11, "col": 11, "symbol": "use_after"},
            {"rule": "use-after-donate", "line": 15, "col": 25, "symbol": "loop_carried"},
            {"rule": "use-after-donate", "line": 29, "col": 0, "symbol": "Engine.leak"},
        ],
        "lk2": [
            {"rule": "lock-order", "line": 16, "col": 0, "symbol": "Worker.ab"},
            {"rule": "lock-order", "line": 21, "col": 0, "symbol": "Worker.ba"},
            {"rule": "lock-order", "line": 26, "col": 19, "symbol": "Worker.slow"},
            {"rule": "lock-order", "line": 30, "col": 19, "symbol": "Worker.chain"},
        ],
        "async": [
            {"rule": "async-blocking", "line": 15, "col": 15, "symbol": "build.handler"},
            {"rule": "async-blocking", "line": 24, "col": 4, "symbol": "sleepy"},
        ],
    }
    sources = {"don": DONATE_REPRO, "lk2": LOCK_ORDER_REPRO, "async": ASYNC_REPRO}
    for name, expected in golden.items():
        report = _lint_source(tmp_path, name, sources[name]).report()
        got = [
            {k: entry[k] for k in ("rule", "line", "col", "symbol")}
            for entry in report["findings"]
        ]
        assert got == expected, f"{name}: {json.dumps(got, indent=2)}"
        assert report["counts"]["findings"] == len(expected)


# --------------------------------------------------- suppression anchoring


def test_suppression_on_last_line_of_multiline_statement(tmp_path):
    """The finding sits on an inner physical line; the suppression comment on
    the statement's closing line. Logical-line anchoring matches them."""
    source = (
        "import jax\n\n"
        "@jax.jit\n"
        "def traced(x):\n"
        "    return (\n"
        "        x.sum()\n"
        "        .item()\n"
        "    )  # graftlint: disable=host-sync -- fixture: statement-level suppression\n"
    )
    result = _lint_source(tmp_path, "ml", source)
    assert result.ok, [f.format() for f in result.findings]
    assert len(result.suppressed) == 1
    # the physical lines differ — only the anchors agree (v1 matched raw lines
    # and provably missed this)
    assert result.suppressed[0].line != 8


def test_suppression_above_decorated_def_covers_the_signature(tmp_path):
    """A standalone suppression ABOVE the decorator anchors to the decorated
    def's logical start, covering findings on any signature line."""
    source = (
        "import functools\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n\n"
        "def make(devs):\n"
        "    return Mesh(np.asarray(devs), ('data', 'tensor'))\n\n"
        "# graftlint: disable=sharding -- fixture: decorated-def anchoring\n"
        "@functools.lru_cache\n"
        "def layout(\n"
        "    mesh,\n"
        "    spec=P('tensr'),\n"
        "):\n"
        "    return NamedSharding(mesh, spec)\n"
    )
    result = _lint_source(tmp_path, "dec", source)
    assert result.ok, [f.format() for f in result.findings]
    assert len(result.suppressed) == 1
    assert result.suppressed[0].rule == "sharding"


# ----------------------------------------------------------------- baseline


def test_baseline_silences_recorded_findings_but_not_new_ones(tmp_path):
    from unionml_tpu.analysis import baseline_payload, load_baseline, run_lint

    f = tmp_path / "legacy.py"
    f.write_text(DONATE_REPRO)
    first = run_lint([str(f)])
    assert len(first.findings) == 3
    baseline_file = tmp_path / "baseline.json"
    baseline_file.write_text(json.dumps(baseline_payload(first.findings)))

    # same tree + baseline: clean, findings inventoried as baselined
    second = run_lint([str(f)], baseline=load_baseline(str(baseline_file)))
    assert second.ok
    assert len(second.baselined) == 3

    # a NEW hazard is not silenced by the old inventory
    f.write_text(DONATE_REPRO + "\n\ndef fresh(state, b):\n    o, l = step(state, b)\n    return state\n")
    third = run_lint([str(f)], baseline=load_baseline(str(baseline_file)))
    assert len(third.findings) == 1
    assert third.findings[0].symbol == "fresh"
    assert len(third.baselined) == 3


def test_baseline_fingerprints_survive_line_moves(tmp_path):
    """Inserting unrelated lines above must not invalidate the inventory —
    fingerprints are line-independent."""
    from unionml_tpu.analysis import baseline_payload, load_baseline, run_lint

    f = tmp_path / "moved.py"
    f.write_text(DONATE_REPRO)
    payload = baseline_payload(run_lint([str(f)]).findings)
    baseline_file = tmp_path / "baseline.json"
    baseline_file.write_text(json.dumps(payload))
    f.write_text("# a new header comment\nUNRELATED = 1\n" + DONATE_REPRO)
    shifted = run_lint([str(f)], baseline=load_baseline(str(baseline_file)))
    assert shifted.ok, [fi.format() for fi in shifted.findings]
    assert len(shifted.baselined) == 3


# -------------------------------------------------------------------- SARIF


def test_sarif_output_validates_against_sarif_2_1_0_schema(tmp_path):
    """The emitted document validates against the SARIF 2.1.0 schema
    (structural subset of the OASIS schema, vendored next to this test)."""
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")

    schema = json.loads(
        (pathlib.Path(__file__).parent / "sarif_2_1_0_schema.json").read_text()
    )
    for name, source in [
        ("don", DONATE_REPRO), ("lk2", LOCK_ORDER_REPRO),
        ("async", ASYNC_REPRO), ("sup", SUPPRESSED), ("ok", CLEAN),
    ]:
        doc = _lint_source(tmp_path, name, source).sarif()
        jsonschema.validate(doc, schema)
        assert doc["version"] == "2.1.0"


def test_sarif_content_levels_rules_and_suppressions(tmp_path):
    result = _lint_source(tmp_path, "sarif_don", DONATE_REPRO)
    doc = result.sarif()
    run = doc["runs"][0]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    # the full catalog rides along, including the always-on meta rules
    assert {"use-after-donate", "lock-order", "async-blocking", "host-sync",
            "suppression", "parse"} <= rules
    results = run["results"]
    assert len(results) == 3 and all(r["level"] == "error" for r in results)
    for r in results:
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("sarif_don.py")
        assert loc["region"]["startLine"] >= 1 and loc["region"]["startColumn"] >= 1
        assert r["partialFingerprints"]["graftlint/v1"]
    # suppressed findings carry the author's reason into the SARIF suppression
    sup_doc = _lint_source(tmp_path, "sarif_sup", SUPPRESSED).sarif()
    sup_results = sup_doc["runs"][0]["results"]
    assert len(sup_results) == 1
    assert sup_results[0]["level"] == "note"
    assert sup_results[0]["suppressions"][0]["kind"] == "inSource"
    assert "known-safe" in sup_results[0]["suppressions"][0]["justification"]


def test_cli_writes_sarif_and_enforces_budget(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(RETRACE_REPRO)
    out = tmp_path / "report.sarif"
    assert lint_main([str(bad), "--sarif", str(out)]) == 1
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0" and doc["runs"][0]["results"]
    # a clean file under an absurdly tight budget fails on wall time alone
    ok = tmp_path / "ok.py"
    ok.write_text(CLEAN)
    assert lint_main([str(ok), "--budget", "0.000001"]) == 1
    assert lint_main([str(ok), "--budget", "600"]) == 0
    captured = capsys.readouterr()
    assert "wall" in captured.out or "wall" in captured.err


def test_cli_baseline_roundtrip(tmp_path, capsys):
    legacy = tmp_path / "legacy.py"
    legacy.write_text(DONATE_REPRO)
    baseline = tmp_path / "base.json"
    assert lint_main([str(legacy), "--write-baseline", str(baseline)]) == 0
    assert lint_main([str(legacy), "--baseline", str(baseline)]) == 0
    legacy.write_text(DONATE_REPRO + "\n\ndef fresh(state, b):\n    o, l = step(state, b)\n    return state\n")
    assert lint_main([str(legacy), "--baseline", str(baseline)]) == 1
    capsys.readouterr()


# ------------------------------------------- the rule catalogs stay in sync


def test_new_rule_families_are_registered_and_listable(capsys):
    from unionml_tpu.analysis.core import RULES, _load_rule_modules

    _load_rule_modules()
    assert {"use-after-donate", "lock-order", "async-blocking"} <= set(RULES)
    assert lint_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for name in ("use-after-donate", "lock-order", "async-blocking"):
        assert name in listing


def test_mutated_engine_rebind_is_caught():
    """Tree-grounded regression: drop ONE rebind from the REAL decode engine
    source (the chunked-prefill cache donation) and the donation rule must
    catch it — the discipline the serving engine depends on is mechanically
    enforced, not reviewer folklore."""
    import pathlib
    import tempfile

    from unionml_tpu.analysis import run_lint as _run

    src = (
        pathlib.Path(__file__).resolve().parent.parent.parent
        / "unionml_tpu" / "serving" / "continuous.py"
    ).read_text()
    mutated = src.replace(
        'last, state["cache"] = self._chunk_fn(', 'last, _ignored = self._chunk_fn(', 1
    )
    assert mutated != src, "the chunked-prefill rebind moved; update this mutation"
    with tempfile.TemporaryDirectory() as d:
        f = pathlib.Path(d) / "continuous.py"
        f.write_text(mutated)
        result = _run([str(f)], ["use-after-donate"])
    assert any(
        f.rule == "use-after-donate" and "state['cache']" in f.message
        for f in result.findings
    ), [f.format() for f in result.findings]


# ----------------------------------------- resource lifetime (graftlint v3)
# (cfg + rules_resources: leak-on-exception-path, double-release,
# unbalanced-transfer, and the owns/transfers/holds contract comments)

RESOURCE_REPRO = '''
class Batcher:
    def __init__(self, prefix_cache, telemetry):
        self.prefix_cache = prefix_cache
        self.telemetry = telemetry

    def leak_on_raise(self, path, slot):
        self.prefix_cache.pin(path)
        self.bookkeep(slot)
        return path

    def bookkeep(self, slot):
        raise RuntimeError(slot)

    def span_leak(self, rid, payload):
        trace = self.telemetry.new_trace(rid)
        if payload is None:
            return None
        self.telemetry.end_trace(trace)
        return trace

    def double(self, path):
        self.prefix_cache.release(path)
        self.prefix_cache.release(path)

    # transfers: kv-pin
    def bad_transfer(self, path):
        self.prefix_cache.pin(path)
        self.prefix_cache.unpin(path)
        return path

    # owns: kv-pin
    def broken_owner(self, path):
        self.log(path)

    def log(self, path):
        pass
'''

RESOURCE_CLEAN = '''
class Batcher:
    def __init__(self, prefix_cache, telemetry):
        self.prefix_cache = prefix_cache
        self.telemetry = telemetry

    def fixed(self, path, slot):
        self.prefix_cache.pin(path)
        try:
            self.bookkeep(slot)
        except Exception:
            self.prefix_cache.unpin(path)
            raise
        return path

    def bookkeep(self, slot):
        raise RuntimeError(slot)

    def span_balanced(self, rid):
        trace = self.telemetry.new_trace(rid)
        try:
            self.bookkeep(rid)
        finally:
            self.telemetry.end_trace(trace)

    def double_ok(self, path, tokens):
        self.prefix_cache.release(path)
        path, extra = self.prefix_cache.match(tokens)
        self.prefix_cache.release(path)
        return extra

    # transfers: kv-pin
    def hands_over(self, path):
        self.prefix_cache.pin(path)
        return path

    # owns: kv-pin
    def good_owner(self, path):
        self.prefix_cache.unpin(path)

    def escapes_to_state(self, registry, path):
        self.prefix_cache.pin(path)
        registry[path] = 1
        self.bookkeep(path)

    def with_is_not_an_acquire(self, p):
        with open(p) as fh:
            return fh.read()
'''


def test_resource_repro_fires_all_three_shapes(tmp_path):
    result = _lint_source(tmp_path, "rsrc", RESOURCE_REPRO)
    assert {f.rule for f in result.findings} == {
        "resource-leak", "double-release", "unbalanced-transfer",
    }
    by_symbol = {f.symbol: f for f in result.findings}
    # exception-path leak names the noun and carries a line witness
    leak = by_symbol["Batcher.leak_on_raise"]
    assert "exception path" in leak.message and "->" in leak.message
    # normal-exit trace leak (the early return skips end_trace)
    span = by_symbol["Batcher.span_leak"]
    assert "end_trace" in span.message
    # double-release points at the second release and the first's line
    dbl = by_symbol["Batcher.double"]
    assert dbl.rule == "double-release" and "already released" in dbl.message
    # a transfers-annotated function that ALSO releases is flagged there
    xfer = by_symbol["Batcher.bad_transfer"]
    assert xfer.rule == "unbalanced-transfer"
    # an owns-annotated function that never releases breaks the contract
    assert "owns: kv-pin" in by_symbol["Batcher.broken_owner"].message


def test_resource_clean_twin_is_finding_free(tmp_path):
    """Each repro shape's fixed form: release-on-error handler, finally-based
    trace balance, re-acquire between releases, honored transfer/owns
    contracts, escape-into-state, and ``with`` (context managers release
    their own resource)."""
    result = _lint_source(tmp_path, "rsrc_ok", RESOURCE_CLEAN)
    assert result.ok, [f.format() for f in result.findings]


def test_resource_golden_report(tmp_path):
    """Machine-readable pin for the resource family: rule ids, lines, columns,
    symbols — the exact shape CI tooling consumes."""
    expected = [
        {"rule": "resource-leak", "line": 8, "col": 8, "symbol": "Batcher.leak_on_raise"},
        {"rule": "resource-leak", "line": 16, "col": 16, "symbol": "Batcher.span_leak"},
        {"rule": "double-release", "line": 24, "col": 8, "symbol": "Batcher.double"},
        {"rule": "unbalanced-transfer", "line": 29, "col": 8, "symbol": "Batcher.bad_transfer"},
        {"rule": "resource-leak", "line": 33, "col": 4, "symbol": "Batcher.broken_owner"},
    ]
    report = _lint_source(tmp_path, "rsrc", RESOURCE_REPRO).report()
    got = [
        {k: entry[k] for k in ("rule", "line", "col", "symbol")}
        for entry in report["findings"]
    ]
    assert got == expected, json.dumps(got, indent=2)
    assert report["counts"]["findings"] == len(expected)


def test_resource_rules_are_registered_and_listable(capsys):
    from unionml_tpu.analysis.core import RULES, _load_rule_modules

    _load_rule_modules()
    assert {"resource-leak", "double-release", "unbalanced-transfer"} <= set(RULES)
    assert lint_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for name in ("resource-leak", "double-release", "unbalanced-transfer"):
        assert name in listing


def test_resource_sarif_validates_and_catalogs_the_family(tmp_path):
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    schema = json.loads(
        (pathlib.Path(__file__).parent / "sarif_2_1_0_schema.json").read_text()
    )
    doc = _lint_source(tmp_path, "rsrc", RESOURCE_REPRO).sarif()
    jsonschema.validate(doc, schema)
    rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"resource-leak", "double-release", "unbalanced-transfer"} <= rules
    hit = {r["ruleId"] for r in doc["runs"][0]["results"]}
    assert hit == {"resource-leak", "double-release", "unbalanced-transfer"}


SWALLOWED_CLEAN_V3 = '''
def best_effort_teardown(sub, fh):
    try:
        sub.unsubscribe()
        fh.close()
    except Exception:
        pass

def fallback_value(probe):
    try:
        raw = probe()
    except Exception:
        raw = {}
    return raw

def release_on_error(cache, path, slot):
    cache.pin(path)
    try:
        note(slot)
    except Exception:
        cache.unpin(path)
        failed = True
    return path
'''


def test_swallowed_exception_v3_exempts_handling_by_construction(tmp_path):
    """The three CFG-aware exemptions: best-effort release teardown, fallback
    binding, and a release-on-error handler whose every exit path releases —
    none needs a suppression anymore (the resource family also stays quiet:
    the handler IS the release path it demands)."""
    result = _lint_source(tmp_path, "sw3", SWALLOWED_CLEAN_V3)
    assert result.ok, [f.format() for f in result.findings]


@pytest.mark.parametrize(
    "label, old, new, symbol, witness",
    [
        (
            "unpin-in-discard_salvage",
            "                self.prefix_cache.unpin(rec.path)\n",
            "                pass\n",
            "DecodeEngine.discard_salvage",
            "relied on by",
        ),
        (
            "unpin-in-release_preempted",
            "            self.prefix_cache.unpin(state.path)\n",
            "            pass\n",
            "DecodeEngine.release_preempted",
            "ContinuousBatcher._maybe_preempt",
        ),
        (
            "end_trace-in-_tel_end",
            "        self._telemetry.end_trace(ticket.request_id, status, reason=reason)\n",
            "        pass\n",
            "ContinuousBatcher._tel_end",
            "owns: trace",
        ),
        (
            "discard-in-_capture_salvage",
            "        self.discard_salvage()  # a prior incident's uncollected records\n",
            "",
            "DecodeEngine._capture_salvage",
            "holds: kv-pin",
        ),
    ],
)
def test_mutated_serving_release_path_is_caught(label, old, new, symbol, witness):
    """Tree-grounded regressions, one per resource class: delete a single
    release from the REAL serving source and the resource family must
    produce EXACTLY ONE finding naming the broken function — the leak
    contracts are mechanically enforced, not reviewer folklore."""
    import pathlib
    import tempfile

    from unionml_tpu.analysis import run_lint as _run

    src = (
        pathlib.Path(__file__).resolve().parent.parent.parent
        / "unionml_tpu" / "serving" / "continuous.py"
    ).read_text()
    mutated = src.replace(old, new, 1)
    assert mutated != src, f"{label}: the release moved; update this mutation"
    with tempfile.TemporaryDirectory() as d:
        f = pathlib.Path(d) / "continuous.py"
        f.write_text(mutated)
        result = _run(
            [str(f)], ["resource-leak", "double-release", "unbalanced-transfer"]
        )
    assert len(result.findings) == 1, [x.format() for x in result.findings]
    (finding,) = result.findings
    assert finding.symbol == symbol
    assert witness in finding.message


# ======================================================== graftlint v4: races
# (threads + rules_races: thread-role inference feeding a lock-set data-race
# detector plus the check-then-act / lock-leaf / fires-outside-lock contracts)

RACES_REPRO = '''
import threading

class Pipeline:
    def __init__(self):
        self._lock = threading.Lock()
        self.depth = 0  # guarded-by: _lock
        self.peak = 0
        self._thread = threading.Thread(target=self._worker, name="drainer")
        self._thread.start()

    def _worker(self):
        while True:
            with self._lock:
                self.depth -= 1
            if self.peak > 0:
                self.peak -= 1

    def submit(self, item):
        with self._lock:
            self.depth += 1
        if self.depth > 8:
            raise RuntimeError(item)
        self.peak = max(self.peak, self.depth)

    def collapse(self):
        with self._lock:
            if self.depth == 0:
                drained = True
            else:
                drained = False
        if drained:
            with self._lock:
                self.depth = -1
        return drained
'''

LOCK_LEAF_REPRO = '''
import threading
import time

class Telemetry:
    def __init__(self):
        self._stats_lock = threading.Lock()  # lock-leaf
        self._journal_lock = threading.Lock()
        self.counters = {}

    def bump(self, key):
        with self._stats_lock:
            with self._journal_lock:
                self.counters[key] = 1

    def flush(self):
        with self._stats_lock:
            time.sleep(0.1)

    def drain(self):
        with self._stats_lock:
            self._persist()

    def _persist(self):
        with self._journal_lock:
            pass
'''

CALLBACK_REPRO = '''
import threading

class Supervisor:
    def __init__(self):
        self._lock = threading.Lock()
        self._subscribers = []
        self._state = "idle"

    def subscribe(self, callback):  # fires-outside-lock
        self._subscribers.append(callback)

    def transition(self, state):
        with self._lock:
            old, self._state = self._state, state
            for cb in list(self._subscribers):
                cb(old, state)
'''

RACES_CLEAN = '''
import threading

class Pipeline:
    def __init__(self):
        self._lock = threading.Lock()  # lock-leaf
        self.depth = 0  # guarded-by: _lock
        self._subscribers = []
        self._thread = threading.Thread(target=self._worker, name="drainer")
        self._thread.start()

    def subscribe(self, callback):  # fires-outside-lock
        self._subscribers.append(callback)

    def _worker(self):
        while True:
            with self._lock:
                self.depth -= 1

    def submit(self, item):
        with self._lock:
            self.depth += 1
            deep = self.depth > 8
        if deep:
            raise RuntimeError(item)

    def collapse(self):
        with self._lock:
            if self.depth == 0:
                self.depth = -1
                return True
        return False

    def _notify(self, state):
        for cb in list(self._subscribers):
            cb(state)
'''


def test_data_race_repro_fires_with_thread_role_witnesses(tmp_path):
    result = _lint_source(tmp_path, "races", RACES_REPRO)
    assert {f.rule for f in result.findings} == {"data-race", "check-then-act"}
    by_symbol = {f.symbol: f for f in result.findings}
    # lock-set violation: no lock EVER guards self.peak, flagged once at the
    # first write with both thread roles named
    peak = by_symbol["Pipeline._worker"]
    assert "self.peak" in peak.message
    assert "thread:drainer" in peak.message and "api" in peak.message
    assert "NO lock is ever held" in peak.message
    # guarded-by contract: the declared lock is simply missing at this read
    guarded = by_symbol["Pipeline.submit"]
    assert "guarded-by: _lock" in guarded.message and "without" in guarded.message
    # check-then-act: condition checked under one hold region, acted on under
    # a separate one — the finding cites the stale read's line
    cta = by_symbol["Pipeline.collapse"]
    assert cta.rule == "check-then-act" and "line 28" in cta.message


def test_lock_leaf_repro_fires_all_three_shapes(tmp_path):
    result = _lint_source(tmp_path, "leaf", LOCK_LEAF_REPRO, rules=["lock-leaf"])
    by_symbol = {f.symbol: f.message for f in result.findings}
    assert "a leaf lock must stay the innermost lock" in by_symbol["Telemetry.bump"]
    assert "time.sleep() sleeps the thread" in by_symbol["Telemetry.flush"]
    # interprocedural: the acquisition hides one call away
    assert "Telemetry._persist()" in by_symbol["Telemetry.drain"]


def test_callback_under_lock_repro_fires(tmp_path):
    result = _lint_source(tmp_path, "cb", CALLBACK_REPRO)
    (finding,) = result.findings
    assert finding.rule == "callback-under-lock"
    assert finding.symbol == "Supervisor.transition"
    assert "Supervisor.subscribe" in finding.message
    assert "fires-outside-lock" in finding.message


def test_races_clean_twin_is_finding_free(tmp_path):
    """Each repro's fixed form: the check moved under the SAME hold region,
    honest leaf locks, and callbacks fired after the lock is dropped — plus
    the contract annotations themselves lint clean."""
    result = _lint_source(tmp_path, "races_ok", RACES_CLEAN)
    assert result.ok, [f.format() for f in result.findings]


def test_races_golden_report(tmp_path):
    """Machine-readable pin for the races family (full catalog run: the
    blocking-leaf repro legitimately trips lock-order too — the families
    overlap by design, each naming its own contract)."""
    expected = [
        {"rule": "data-race", "line": 17, "col": 16, "symbol": "Pipeline._worker"},
        {"rule": "data-race", "line": 22, "col": 11, "symbol": "Pipeline.submit"},
        {"rule": "check-then-act", "line": 34, "col": 16, "symbol": "Pipeline.collapse"},
    ]
    report = _lint_source(tmp_path, "races", RACES_REPRO).report()
    got = [
        {k: entry[k] for k in ("rule", "line", "col", "symbol")}
        for entry in report["findings"]
    ]
    assert got == expected, json.dumps(got, indent=2)
    leaf_expected = [
        {"rule": "lock-leaf", "line": 13, "symbol": "Telemetry.bump"},
        {"rule": "lock-leaf", "line": 18, "symbol": "Telemetry.flush"},
        {"rule": "lock-order", "line": 18, "symbol": "Telemetry.flush"},
        {"rule": "lock-leaf", "line": 22, "symbol": "Telemetry.drain"},
    ]
    leaf_report = _lint_source(tmp_path, "leaf", LOCK_LEAF_REPRO).report()
    leaf_got = [
        {k: entry[k] for k in ("rule", "line", "symbol")}
        for entry in leaf_report["findings"]
    ]
    assert leaf_got == leaf_expected, json.dumps(leaf_got, indent=2)


def test_races_rules_are_registered_and_listable(capsys):
    from unionml_tpu.analysis.core import RULES, families

    catalog = families()
    assert set(catalog["races"]) == {
        "data-race", "check-then-act", "lock-leaf", "callback-under-lock",
    }
    for name in catalog["races"]:
        assert RULES[name].family == "races"
    assert lint_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for name in ("data-race", "check-then-act", "lock-leaf", "callback-under-lock"):
        assert name in listing


def test_races_sarif_validates_and_catalogs_the_family(tmp_path):
    import pathlib

    jsonschema = pytest.importorskip("jsonschema")
    schema = json.loads(
        (pathlib.Path(__file__).parent / "sarif_2_1_0_schema.json").read_text()
    )
    doc = _lint_source(tmp_path, "races", RACES_REPRO).sarif()
    jsonschema.validate(doc, schema)
    rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"data-race", "check-then-act", "lock-leaf", "callback-under-lock"} <= rules
    hit = {r["ruleId"] for r in doc["runs"][0]["results"]}
    assert hit == {"data-race", "check-then-act"}


# ------------------------------------------------- the v4 CLI: --only / --paths


def test_cli_only_family_selects_whole_families(tmp_path, capsys):
    bad = tmp_path / "leafbad.py"
    bad.write_text(LOCK_LEAF_REPRO)
    assert lint_main([str(bad), "--only", "races"]) == 1
    # out-of-family rules don't run: sharding has nothing to say here
    assert lint_main([str(bad), "--only", "sharding"]) == 0
    # unknown family names the catalog and exits 2 (bad invocation, not dirty)
    assert lint_main([str(bad), "--only", "nosuch"]) == 2
    err = capsys.readouterr().err
    assert "unknown family" in err and "races" in err
    # --rules and --only cannot be combined
    assert lint_main([str(bad), "--rules", "data-race", "--only", "races"]) == 2
    capsys.readouterr()


def test_cli_paths_restricts_reporting_not_the_scan(tmp_path, capsys):
    bad = tmp_path / "cbbad.py"
    bad.write_text(CALLBACK_REPRO)
    ok = tmp_path / "fine.py"
    ok.write_text(CLEAN)
    # the full scan fails; restricted to the clean file the same scan exits 0
    assert lint_main([str(tmp_path)]) == 1
    assert lint_main([str(tmp_path), "--paths", str(ok)]) == 0
    assert lint_main([str(tmp_path), "--paths", str(bad)]) == 1
    capsys.readouterr()


def test_cli_timings_prints_per_family_wall_time(tmp_path, capsys):
    ok = tmp_path / "ok.py"
    ok.write_text(CLEAN)
    assert lint_main([str(ok), "--timings"]) == 0
    out = capsys.readouterr().out
    assert "parse" in out and "races" in out


# ----------------------------- tree-grounded mutations: the races family
# detects a real deleted guard (the PR-landing acceptance for v4)


@pytest.mark.parametrize(
    "label, filename, companions, old, new, rules, symbol, witness",
    [
        (
            "requeue-guard-in-adopt_ticket",
            "continuous.py",
            (),
            '        with self._lock:\n'
            '            if self._closed:\n'
            '                raise EngineFailure("batcher is closed", reason="batcher_closed")\n'
            '            self.scheduler.requeue(ticket, preemption=False)\n',
            '        if True:\n'
            '            if self._closed:\n'
            '                raise EngineFailure("batcher is closed", reason="batcher_closed")\n'
            '            self.scheduler.requeue(ticket, preemption=False)\n',
            ["data-race"],
            "ContinuousBatcher.adopt_ticket",
            "thread:continuous-batcher",
        ),
        (
            "session-map-guard-in-session_replica",
            "fleet.py",
            ("supervisor.py",),
            '        with self._lock:\n'
            '            entry = self._sessions.get(session_id)\n',
            '        if True:\n'
            '            entry = self._sessions.get(session_id)\n',
            ["data-race"],
            "Router.session_replica",
            "thread:engine-watchdog",
        ),
        (
            "notify-moved-under-lock-in-note_failure",
            "supervisor.py",
            (),
            '            new = self._state\n        self._notify(old, new)\n',
            '            new = self._state\n            self._notify(old, new)\n',
            ["callback-under-lock"],
            "EngineSupervisor.note_failure",
            "fires-outside-lock",
        ),
        (
            "sleep-injected-into-leaf-hold-region",
            "telemetry.py",
            (),
            '        with self._lock:\n'
            '            trace = self._active.pop(request_id, None)\n',
            '        with self._lock:\n'
            '            time.sleep(0.001)\n'
            '            trace = self._active.pop(request_id, None)\n',
            ["lock-leaf"],
            "Telemetry.end_trace",
            "lock-leaf",
        ),
    ],
)
def test_mutated_serving_guard_is_caught(label, filename, companions, old, new,
                                         rules, symbol, witness):
    """Tree-grounded regressions for v4: break ONE concurrency guard in the
    REAL serving source and the races family must produce EXACTLY ONE finding
    naming the broken function, with its thread-role witness — the fleet's
    locking discipline is mechanically enforced, not reviewer folklore.
    (fleet.py lints together with supervisor.py: the watchdog thread role
    reaches the Router through the supervisor's subscriber registry.)"""
    import pathlib
    import shutil
    import tempfile

    from unionml_tpu.analysis import run_lint as _run

    serving = (
        pathlib.Path(__file__).resolve().parent.parent.parent
        / "unionml_tpu" / "serving"
    )
    src = (serving / filename).read_text()
    mutated = src.replace(old, new, 1)
    assert mutated != src, f"{label}: the guard moved; update this mutation"
    with tempfile.TemporaryDirectory() as d:
        scope = [pathlib.Path(d) / filename]
        scope[0].write_text(mutated)
        for companion in companions:
            scope.append(pathlib.Path(d) / companion)
            shutil.copy(serving / companion, scope[-1])
        result = _run([str(p) for p in scope], rules)
    assert len(result.findings) == 1, [x.format() for x in result.findings]
    (finding,) = result.findings
    assert finding.symbol == symbol
    assert witness in finding.message
