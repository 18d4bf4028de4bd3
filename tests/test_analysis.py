"""prime-lint (prime_tpu/analysis) — fixture tests per rule, waiver/pragma
suppression, the catalog-mode exposition lint, and the real-tree gate.

Each checker is driven through an in-memory Project so the fixtures are
visible next to their assertions; the final tests run the full suite over
the actual repo and assert it is clean modulo the checked-in baseline —
the same contract CI's `analysis` job enforces via
`python -m prime_tpu.analysis --check`.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

import pytest

from prime_tpu.analysis import (
    DEFAULT_BASELINE,
    apply_baseline,
    jit_boundary,
    knob_registry,
    load_baseline,
    lock_discipline,
    obs_contract,
    run_checks,
)
from prime_tpu.analysis.core import Project, Waiver

REPO_ROOT = Path(__file__).resolve().parent.parent


def project(src: str, path: str = "prime_tpu/serve/mod.py", docs: dict | None = None):
    return Project({path: textwrap.dedent(src)}, docs=docs)


def rules_of(findings):
    return [f.rule for f in findings]


# ---- lock-discipline --------------------------------------------------------


LOCKED_CLASS = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
        self._n = 0

    def add(self, x):
        with self._lock:
            self._items.append(x)
            self._n += 1
"""


def test_lock_unlocked_read_is_flagged():
    findings = lock_discipline.check(
        project(LOCKED_CLASS + "\n    def peek(self):\n        return self._items[-1]\n")
    )
    assert [f.symbol for f in findings] == ["C._items"]
    assert findings[0].rule == "lock-discipline"


def test_lock_clean_class_passes():
    findings = lock_discipline.check(
        project(
            LOCKED_CLASS
            + "\n    def peek(self):\n        with self._lock:\n            return self._items[-1]\n"
        )
    )
    assert findings == []


def test_lock_held_docstring_helper_is_recognized():
    src = LOCKED_CLASS + '''
    def _drop(self):
        """Remove the tail. Caller holds the lock."""
        self._items.pop()
'''
    assert lock_discipline.check(project(src)) == []


def test_lock_threadsafe_containers_exempt():
    src = """
    import queue, threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()

        def push(self, x):
            with self._lock:
                self._q.put(x)

        def pop(self):
            return self._q.get()
    """
    assert lock_discipline.check(project(src)) == []


def test_lock_nested_def_under_with_is_not_locked():
    # a closure defined under the lock runs later, when the lock is free
    src = LOCKED_CLASS + """
    def make_reader(self):
        with self._lock:
            def reader():
                return self._items[-1]
        return reader
"""
    findings = lock_discipline.check(project(src))
    assert [f.symbol for f in findings] == ["C._items"]


def test_lock_outer_self_alias_nested_class():
    # the serve server idiom: outer = self handed to a nested handler class
    src = """
    import threading

    class S:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            outer = self

            class Handler:
                def inc(self):
                    with outer._lock:
                        outer._count += 1

                def bad_read(self):
                    return outer._count

            self.handler_cls = Handler

        def snapshot(self):
            return self._count
    """
    findings = lock_discipline.check(project(src))
    assert sorted((f.symbol, f.rule) for f in findings) == [
        ("S._count", "lock-discipline"),
        ("S._count", "lock-discipline"),
    ]
    # both the nested handler's unlocked read and the method read are hit
    labels = sorted(f.message.split(" touches")[0] for f in findings)
    assert labels == ["S.__init__.bad_read", "S.snapshot"]


def test_pragma_suppresses_any_rule_centrally():
    # pragmas are applied once in run_checks, for every checker uniformly
    src = LOCKED_CLASS + (
        "\n    def peek(self):\n"
        "        return self._items[-1]  # prime-lint: ignore[lock-discipline] benign\n"
    )
    assert run_checks(project(src), ["lock"]) == []
    knob = """
    import os

    def f():
        return os.environ.get("PRIME_X")  # prime-lint: ignore[knob-direct-read, knob-undocumented] legacy
    """
    doc = "| env | CLI flag | default |\n|---|---|---|\n"
    assert (
        run_checks(project(knob, docs={"docs/architecture.md": doc}), ["knobs"]) == []
    )


# ---- jit boundary -----------------------------------------------------------


JIT_CLASS = """
import jax, time

class E:
    def _make(self):
        def run(params, state):
            return state
        return jax.jit(run, donate_argnums=(1,))

    def setup(self):
        self._fn = self._make()
"""


def test_jit_purity_flags_host_state():
    src = """
    import jax, time

    def builder():
        def run(x):
            t = time.monotonic()
            print(x)
            return x
        return jax.jit(run)
    """
    findings = jit_boundary.check(project(src))
    offenders = {f.symbol for f in findings}
    assert offenders == {"run:time.monotonic", "run:print"}
    assert all(f.rule == "jit-purity" for f in findings)


def test_jit_purity_decorated_partial():
    src = """
    import jax, os
    from functools import partial

    @partial(jax.jit, static_argnums=(1,))
    def run(x, n):
        if os.environ.get("PRIME_X"):
            return x
        return x + n
    """
    findings = [f for f in jit_boundary.check(project(src)) if f.rule == "jit-purity"]
    assert [f.symbol for f in findings] == ["run:os.environ.get"]


def test_jit_purity_obs_layer_flagged_and_pure_fn_clean():
    src = """
    import jax

    class E:
        def _make(self):
            def run(params, state):
                self._m_tokens.inc()
                return state
            return jax.jit(run)

        def _make_pure(self):
            def pure(params, state):
                return params + state
            return jax.jit(pure)
    """
    findings = jit_boundary.check(project(src))
    assert [f.symbol for f in findings] == ["run:self._m_tokens"]


def test_jit_donation_use_after_donate():
    src = JIT_CLASS + """
    def step(self, state):
        out = self._fn(self.params, state)
        return state
"""
    findings = [f for f in jit_boundary.check(project(src)) if f.rule == "jit-donation"]
    assert [f.symbol for f in findings] == ["step:state"]


def test_jit_donation_rebind_clears():
    src = JIT_CLASS + """
    def step(self, state):
        state = self._fn(self.params, state)
        return state
"""
    assert [f for f in jit_boundary.check(project(src)) if f.rule == "jit-donation"] == []


def test_jit_donation_self_attr_tainted():
    src = JIT_CLASS + """
    def step(self):
        out = self._fn(self.params, self._state)
        return self._state
"""
    findings = [f for f in jit_boundary.check(project(src)) if f.rule == "jit-donation"]
    assert [f.symbol for f in findings] == ["step:self._state"]


def test_jit_donation_local_jit_binding():
    src = """
    import jax

    def caller(g, state):
        f = jax.jit(g, donate_argnums=(0,))
        out = f(state)
        return state
    """
    findings = [f for f in jit_boundary.check(project(src)) if f.rule == "jit-donation"]
    assert [f.symbol for f in findings] == ["caller:state"]


# ---- obs contract -----------------------------------------------------------


OBS_DOC = """
## Metrics catalog

| metric | type | labels |
|---|---|---|
| `serve_good_total` | counter | — |
| `serve_stale_total` | counter | — |
| `serve_kind_seconds` | gauge | — |

### Span catalog

| span | meaning |
|---|---|
| `serve.good` | fine |
| `serve.stale` | row without a code site |
"""

OBS_SRC = """
class E:
    def __init__(self, r, TRACER):
        self._a = r.counter("serve_good_total", "ok")
        self._b = r.counter("serve_missing_total", "no doc row")
        self._c = r.histogram("serve_kind_seconds", "doc says gauge")
        with TRACER.span("serve.good"):
            pass
        TRACER.emit("serve.undocumented", 1.0)
"""


def test_obs_contract_bidirectional():
    p = project(OBS_SRC, docs={"docs/observability.md": OBS_DOC})
    findings = obs_contract.check(p)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f.symbol)
    assert by_rule == {
        "obs-metric-undocumented": ["serve_missing_total"],
        "obs-metric-stale": ["serve_stale_total"],
        "obs-metric-kind-drift": ["serve_kind_seconds"],
        "obs-span-undocumented": ["serve.undocumented"],
        "obs-span-stale": ["serve.stale"],
    }


def test_obs_contract_missing_doc():
    findings = obs_contract.check(project(OBS_SRC, docs={}))
    assert rules_of(findings) == ["obs-catalog-missing"]


def test_obs_doc_fences_do_not_swallow_prose():
    doc = OBS_DOC + '\n```json\n{"name": "serve.x"}\n```\nand `serve.undocumented` in prose\n'
    p = project(OBS_SRC, docs={"docs/observability.md": doc})
    assert "obs-span-undocumented" not in rules_of(obs_contract.check(p))


def test_load_metrics_catalog():
    catalog = obs_contract.load_metrics_catalog(OBS_DOC)
    assert catalog == {
        "serve_good_total": "counter",
        "serve_stale_total": "counter",
        "serve_kind_seconds": "gauge",
    }


def test_exposition_lint_catalog_mode():
    from prime_tpu.obs.metrics import lint_prometheus_text

    catalog = {"a_total": "counter", "b_seconds": "histogram"}
    ok = "# HELP a_total help\n# TYPE a_total counter\na_total 1\n"
    assert lint_prometheus_text(ok, catalog=catalog) == []
    # type drift vs catalog
    drift = "# HELP a_total h\n# TYPE a_total gauge\na_total 1\n"
    assert any("documents counter" in p for p in lint_prometheus_text(drift, catalog=catalog))
    # exposed family the catalog has never heard of
    unknown = "# HELP x_total h\n# TYPE x_total counter\nx_total 1\n"
    assert any("absent from the metrics catalog" in p for p in lint_prometheus_text(unknown, catalog=catalog))
    # cataloged family exposed without HELP
    nohelp = "# TYPE a_total counter\na_total 1\n"
    assert any("without a HELP line" in p for p in lint_prometheus_text(nohelp, catalog=catalog))
    # no catalog -> classic behavior, none of the above fire
    assert lint_prometheus_text(nohelp) == []


# ---- knob registry ----------------------------------------------------------


KNOB_DOC = """
## Environment knobs

| env | CLI flag | default | meaning |
|---|---|---|---|
| `PRIME_GOOD_FLAG` | — | on | documented and consistent |
| `PRIME_STALE_KNOB` | — | unset | row without any code mention |
| `PRIME_DRIFTY` | — | 5 | code default disagrees |
| `PRIME_PAIRED` | `--paired` | 7 | CLI flag default disagrees |
"""

KNOB_SRC = """
import os
from prime_tpu.core.config import env_flag, env_int

GOOD_DEFAULT = True

def f():
    a = env_flag("PRIME_GOOD_FLAG", GOOD_DEFAULT)
    b = env_int("PRIME_DRIFTY", 9)
    c = env_int("PRIME_PAIRED", 7)
    d = env_int("PRIME_UNDOCUMENTED", 0)
    e = os.environ.get("PRIME_DIRECT")
    return a, b, c, d, e
"""

KNOB_CLI = """
import click

@click.option("--paired", type=int, default=3)
def cmd(paired):
    return paired
"""


def test_knob_registry_rules():
    p = Project(
        {
            "prime_tpu/serve/mod.py": textwrap.dedent(KNOB_SRC),
            "prime_tpu/commands/x.py": textwrap.dedent(KNOB_CLI),
        },
        docs={"docs/architecture.md": KNOB_DOC},
    )
    findings = knob_registry.check(p)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, set()).add(f.symbol)
    assert by_rule == {
        "knob-direct-read": {"PRIME_DIRECT"},
        "knob-undocumented": {"PRIME_UNDOCUMENTED", "PRIME_DIRECT"},
        "knob-stale-doc": {"PRIME_STALE_KNOB"},
        "knob-default-drift": {"PRIME_DRIFTY", "PRIME_PAIRED"},
    }


def test_knob_module_constant_resolution_and_env_write_ok():
    src = """
    import os
    from prime_tpu.core.config import env_int

    DEFAULT = 4

    def f():
        os.environ["PRIME_CHILD"] = "1"   # a write is not a read
        return env_int("PRIME_OK", DEFAULT)
    """
    doc = """
| env | CLI flag | default | meaning |
|---|---|---|---|
| `PRIME_OK` | — | 4 | fine |
| `PRIME_CHILD` | — | unset | exported for children |
"""
    p = project(src, docs={"docs/architecture.md": doc})
    assert knob_registry.check(p) == []


# ---- baseline / waivers -----------------------------------------------------


def test_waiver_suppresses_and_stale_is_reported():
    findings = lock_discipline.check(
        project(LOCKED_CLASS + "\n    def peek(self):\n        return self._items[-1]\n")
    )
    waivers = [
        Waiver("lock-discipline", "prime_tpu/serve/mod.py", "C._items", "ok"),
        Waiver("lock-discipline", "prime_tpu/serve/mod.py", "C._gone", "stale"),
    ]
    active, waived, stale = apply_baseline(findings, waivers)
    assert active == [] and len(waived) == 1
    assert [w.symbol for w in stale] == ["C._gone"]


def test_baseline_requires_reason(tmp_path):
    bad = tmp_path / "baseline.toml"
    bad.write_text('[[waiver]]\nrule = "x"\npath = "y"\nsymbol = "z"\n')
    with pytest.raises(ValueError, match="missing required"):
        load_baseline(bad)


# ---- the real tree ----------------------------------------------------------


def test_real_tree_clean_modulo_baseline():
    """The CI `analysis` job's contract: the repo has no non-waived findings
    and no stale waivers. A checker regression (fixture tests above) and a
    tree regression both fail here."""
    findings = run_checks(Project.from_root(REPO_ROOT))
    waivers = load_baseline(DEFAULT_BASELINE)
    active, _waived, stale = apply_baseline(findings, waivers)
    assert active == [], "non-waived findings:\n" + "\n".join(
        f.render() for f in active
    )
    assert stale == [], "stale waivers: " + ", ".join(w.symbol for w in stale)


def test_real_tree_fixture_violation_fails_check(tmp_path):
    """`--check` exits non-zero the moment a violation is introduced."""
    from prime_tpu.analysis.__main__ import main

    pkg = tmp_path / "prime_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import os\n\ndef f():\n    return os.environ.get('PRIME_PLANTED')\n"
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "architecture.md").write_text("| env | CLI flag | default |\n|---|---|---|\n")
    (docs / "observability.md").write_text("## Metrics catalog\n")
    rc = main(["--check", "--root", str(tmp_path), "--no-baseline"])
    assert rc == 1
    assert main(["--root", str(tmp_path), "--no-baseline"]) == 0  # report mode


def test_cli_rules_subset_leaves_other_waivers_dormant():
    """--rules obs must not report the lock-discipline baseline waiver as
    stale just because the lock checker never ran (regression)."""
    from prime_tpu.analysis.__main__ import main

    assert main(["--check", "--root", str(REPO_ROOT), "--rules", "obs"]) == 0


def test_cli_github_format(tmp_path, capsys):
    from prime_tpu.analysis.__main__ import main

    pkg = tmp_path / "prime_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import os\nX = os.environ.get('PRIME_PLANTED')\n"
    )
    (tmp_path / "docs").mkdir()
    rc = main(["--check", "--root", str(tmp_path), "--format", "github", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=prime_tpu/bad.py" in out
    assert "prime-lint[knob-direct-read]" in out
