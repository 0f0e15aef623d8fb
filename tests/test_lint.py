"""mrlint: driver, the five checkers, pragmas, and the self-check.

Each checker gets at least one TRUE-POSITIVE fixture (a seeded
violation of its review class must be found) and one CLEAN fixture (the
correct idiom must not be flagged) — the checkers guard CI, so both
directions are load-bearing: a silent false negative re-opens the
review class, a false positive teaches people to pragma reflexively.

The self-check at the bottom runs the full analyzer over the shipped
package and asserts zero unsuppressed findings (the ISSUE 11 acceptance
criterion) AND a coverage floor — an entry-detection regression that
silently resolved nothing would also report zero findings, so "clean"
alone proves too little.
"""

import json
import os
import subprocess
import sys
import textwrap

from gpu_mapreduce_tpu import lint
from gpu_mapreduce_tpu.lint.callgraph import CallGraph
from gpu_mapreduce_tpu.lint import purity as _purity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fixture(root, files, rules, docs=None, extra=()):
    """Write a throwaway package under root/pkg (+ optional doc/ files),
    analyze it, return (all findings, unsuppressed findings)."""
    for rel, src in files.items():
        path = os.path.join(root, "pkg", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(src))
    for rel, src in (docs or {}).items():
        path = os.path.join(root, "doc", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(src))
    project = lint.Project(root, package="pkg")
    findings = lint.run(project, rules=rules)
    return findings, [f for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# trace-purity
# ---------------------------------------------------------------------------

PURITY_BAD = """
    import jax
    import time

    def outer(mesh, spec):
        def body(k, v):
            print("traced")          # host effect in traced code
            t = time.time()          # ambient value baked in
            return k + v + t
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                             out_specs=spec)
"""

PURITY_CLEAN = """
    import jax
    import jax.numpy as jnp

    def outer(mesh, spec):
        def body(k, v):
            s = jnp.cumsum(v)
            return k, s
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                             out_specs=(spec, spec))
"""


def test_purity_true_positive(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": PURITY_BAD},
                          ["trace-purity"])
    rules = {f.rule for f in live}
    assert "purity-host-call" in rules
    msgs = " ".join(f.msg for f in live)
    assert "print()" in msgs and "time.time()" in msgs


def test_purity_clean(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": PURITY_CLEAN},
                          ["trace-purity"])
    assert live == []


def test_purity_walks_partial_wrapped_pallas_kernel(tmp_path):
    """The ops/pallas call-site idiom — the kernel body handed to
    ``pallas_call`` wrapped as ``functools.partial(kernel, static...)``
    — is seeded as a traced entry: a host effect inside the kernel
    body must be found (the fixture has the shape of a paged table
    kernel)."""
    src = """
        import functools
        import os
        from jax.experimental import pallas as pl

        def _table_kernel(T, page, base, k_ref, out_ref):
            limit = int(os.environ.get("MRTPU_DEBUG_T", T))  # host read
            out_ref[:] = k_ref[:] + limit

        def run_pages(keys, T, page):
            return pl.pallas_call(
                functools.partial(_table_kernel, T, page, 0),
                out_shape=None,
            )(keys)
    """
    _, live = run_fixture(str(tmp_path), {"mod.py": src},
                          ["trace-purity"])
    assert any(f.rule == "purity-host-call"
               and "_table_kernel" in f.symbol + f.msg
               for f in live), live


def test_knob_registry_sees_fusion_v2_knobs():
    """The fusion-v2 knob routes through utils/env.py and carries a
    doc/settings.md row — the pair the knob-registry rule reconciles
    (any drift re-opens a knob-undocumented/knob-stale finding in the
    self-check below)."""
    with open(os.path.join(REPO, "doc", "settings.md")) as f:
        doc = f.read()
    assert "MRTPU_MEGAFUSE" in doc
    from gpu_mapreduce_tpu.plan.fuser import megafuse_enabled
    assert isinstance(megafuse_enabled(), bool)


def test_purity_clean_partial_pallas_kernel(tmp_path):
    """The same shape with a pure kernel body stays clean."""
    src = """
        import functools
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _table_kernel(T, k_ref, out_ref):
            out_ref[:] = jnp.cumsum(k_ref[:])[:T]

        def run(keys, T):
            return pl.pallas_call(functools.partial(_table_kernel, T),
                                  out_shape=None)(keys)
    """
    _, live = run_fixture(str(tmp_path), {"mod.py": src},
                          ["trace-purity"])
    assert live == []


def test_purity_taint_coercion_and_transitive(tmp_path):
    # float(param) in a helper REACHED from a jit body, param tainted
    # through the call chain; plus a lock acquisition in traced code
    src = """
        import jax
        import threading

        _LOCK = threading.Lock()

        def helper(x):
            return float(x)              # coerces a traced operand

        @jax.jit
        def entry(a, b):
            with _LOCK:                  # trace-time-only lock
                c = helper(a)
            return c + b
    """
    _, live = run_fixture(str(tmp_path), {"mod.py": src},
                          ["trace-purity"])
    rules = {f.rule for f in live}
    assert "purity-coerce" in rules      # float(x) on tainted param
    assert "purity-lock" in rules


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

LOCK_BAD_MUTATION = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.rejects = 0

        def admit(self):
            with self._lock:
                self.rejects += 1

        def fast_path(self):
            self.rejects += 1            # the PR 6 bug class
"""

LOCK_CLEAN = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.rejects = 0

        def admit(self):
            with self._lock:
                self.rejects += 1

        def other(self):
            with self._lock:
                self.rejects += 2
"""

LOCK_CYCLE = """
    import threading

    A = threading.Lock()
    B = threading.Lock()

    def f():
        with A:
            with B:
                pass

    def g():
        with B:
            helper()

    def helper():
        with A:
            pass
"""


def test_lock_unguarded_mutation(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": LOCK_BAD_MUTATION},
                          ["lock-discipline"])
    assert len(live) == 1
    assert live[0].rule == "lock-unguarded-mutation"
    assert "rejects" in live[0].msg
    assert live[0].symbol == "Server.fast_path"


def test_lock_clean(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": LOCK_CLEAN},
                          ["lock-discipline"])
    assert live == []


def test_lock_order_cycle_through_call(tmp_path):
    # f nests A->B syntactically; g holds B and CALLS helper which
    # takes A — the cycle only exists through the callgraph
    _, live = run_fixture(str(tmp_path), {"mod.py": LOCK_CYCLE},
                          ["lock-discipline"])
    assert any(f.rule == "lock-order-cycle" for f in live)
    msg = next(f.msg for f in live if f.rule == "lock-order-cycle")
    assert "A" in msg and "B" in msg


# ---------------------------------------------------------------------------
# cache-key
# ---------------------------------------------------------------------------

CACHEKEY_BAD = """
    import os
    from .cache import CACHE

    def knob():
        return os.environ.get("MRTPU_MODE", "1")

    def builder(mesh):
        mode = knob()                    # read inside the builder...
        return (mesh, mode)

    def cached(mesh):
        return CACHE.get_or_build(
            (mesh,),                     # ...but absent from the key
            lambda: builder(mesh))
"""

CACHEKEY_CLEAN = """
    import os
    from .cache import CACHE

    def knob():
        return os.environ.get("MRTPU_MODE", "1")

    def builder(mesh):
        mode = knob()
        return (mesh, mode)

    def cached(mesh):
        return CACHE.get_or_build(
            (mesh, knob()),              # knob derivable from the key
            lambda: builder(mesh))
"""

CACHE_STUB = """
    class LRU:
        def get_or_build(self, key, build):
            return build()
    CACHE = LRU()
"""

CACHEKEY_LRU = """
    import functools
    import os

    @functools.lru_cache(maxsize=8)
    def builder(mesh):
        mode = os.environ.get("MRTPU_MODE", "1")   # args ARE the key
        return (mesh, mode)
"""


def test_cachekey_true_positive(tmp_path):
    _, live = run_fixture(
        str(tmp_path), {"mod.py": CACHEKEY_BAD, "cache.py": CACHE_STUB},
        ["cache-key"])
    assert len(live) == 1
    f = live[0]
    assert f.rule == "cache-key-missing-knob"
    assert "MRTPU_MODE" in f.msg


def test_cachekey_clean_when_key_derives_knob(tmp_path):
    _, live = run_fixture(
        str(tmp_path),
        {"mod.py": CACHEKEY_CLEAN, "cache.py": CACHE_STUB},
        ["cache-key"])
    assert live == []


def test_cachekey_lru_cache_builder(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": CACHEKEY_LRU},
                          ["cache-key"])
    assert len(live) == 1
    assert "lru_cache" in live[0].msg


CACHEKEY_CAS_BAD = """
    import hashlib
    import os

    def memo_key(payload):
        mode = os.environ.get("MRTPU_MODE", "1")   # changes the result...
        if mode == "0":
            payload = payload.upper()
        return hashlib.sha256(payload.encode()).hexdigest()
"""

CACHEKEY_CAS_CLEAN = """
    import hashlib
    import os

    def memo_key(payload):
        mode = os.environ.get("MRTPU_MODE", "1")
        return hashlib.sha256(
            (payload + mode).encode()).hexdigest()  # knob IS keyed
"""


def test_cachekey_cas_builder_env_read_flagged(tmp_path):
    # idiom 3: a content-address key builder (*_key/*_digest around a
    # hashing call) whose reachable env knob never feeds the digest —
    # two stores could silently share one key across knob states
    _, live = run_fixture(str(tmp_path), {"mod.py": CACHEKEY_CAS_BAD},
                          ["cache-key"])
    assert len(live) == 1
    assert live[0].rule == "cache-key-missing-knob"
    assert "MRTPU_MODE" in live[0].msg


def test_cachekey_cas_builder_clean_when_knob_keyed(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": CACHEKEY_CAS_CLEAN},
                          ["cache-key"])
    assert live == []


# ---------------------------------------------------------------------------
# knob-registry
# ---------------------------------------------------------------------------

KNOBS_BAD = """
    import os
    from .utils.env import env_knob

    def a():
        return os.environ.get("MRTPU_RAW_READ", "1")   # bypass

    def b():
        return env_knob("MRTPU_UNDOCUMENTED", int, 0)  # no doc row
"""

ENV_STUB = """
    import os
    def env_knob(name, cast, default):
        return default
"""

SETTINGS_DOC = """
    | `MRTPU_RAW_READ` | 1 | documented but read raw |
    | `MRTPU_GHOST` | - | documented, read nowhere |
"""


def test_knob_registry(tmp_path):
    _, live = run_fixture(
        str(tmp_path),
        {"mod.py": KNOBS_BAD, "utils/env.py": ENV_STUB},
        ["knob-registry"], docs={"settings.md": SETTINGS_DOC})
    by_rule = {}
    for f in live:
        by_rule.setdefault(f.rule, []).append(f)
    assert any("MRTPU_RAW_READ" in f.msg
               for f in by_rule.get("knob-bypass", []))
    assert any("MRTPU_UNDOCUMENTED" in f.msg
               for f in by_rule.get("knob-undocumented", []))
    stale = by_rule.get("knob-stale", [])
    assert any("MRTPU_GHOST" in f.msg for f in stale)
    assert all(f.path == "doc/settings.md" for f in stale)


def test_knob_registry_clean(tmp_path):
    clean = """
        from .utils.env import env_knob
        def a():
            return env_knob("MRTPU_RAW_READ", int, 1)
    """
    doc = "| `MRTPU_RAW_READ` | 1 | all good |\n"
    _, live = run_fixture(
        str(tmp_path), {"mod.py": clean, "utils/env.py": ENV_STUB},
        ["knob-registry"], docs={"settings.md": doc})
    assert live == []


# ---------------------------------------------------------------------------
# metric-catalog (the migrated check_metrics_doc)
# ---------------------------------------------------------------------------

def test_metric_catalog_fixture(tmp_path):
    files = {"mod.py": 'NAME = "mrtpu_seeded_total"\n'}
    doc = "catalog: `mrtpu_ghost_total` only\n"
    _, live = run_fixture(str(tmp_path), files, ["metric-catalog"],
                          docs={"observability.md": doc})
    rules = sorted(f.rule for f in live)
    assert rules == ["metric-stale", "metric-undocumented"]


def test_metric_catalog_repo_agrees():
    project = lint.Project(REPO)
    live = [f for f in lint.run(project, rules=["metric-catalog"])
            if not f.suppressed]
    assert live == [], [str(f) for f in live]


# ---------------------------------------------------------------------------
# pragmas + baseline
# ---------------------------------------------------------------------------

def test_pragma_suppression_line_and_scope(tmp_path):
    src = """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def locked(self):
                with self._lock:
                    self.n += 1

            def inline(self):
                self.n += 1  # mrlint: disable=lock-unguarded-mutation

            def next_line(self):
                # mrlint: disable=lock-unguarded-mutation — justified
                self.n += 1

            # mrlint: disable=lock-unguarded-mutation — whole scope
            def scoped(self):
                self.n += 1
                self.n += 2

            def still_flagged(self):
                self.n += 1
    """
    findings, live = run_fixture(str(tmp_path), {"mod.py": src},
                                 ["lock-discipline"])
    assert len(live) == 1
    assert live[0].symbol == "S.still_flagged"
    # suppressed findings are still counted, not silently dropped
    assert sum(1 for f in findings if f.suppressed) == 4


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    src = """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def locked(self):
                with self._lock:
                    self.n += 1

            def bare(self):
                self.n += 1  # mrlint: disable=trace-purity
    """
    _, live = run_fixture(str(tmp_path), {"mod.py": src},
                          ["lock-discipline"])
    assert len(live) == 1


def test_module_pragma_after_docstring(tmp_path):
    # the natural header position — right under the module docstring —
    # must cover the whole file
    src = '''
        """Module docstring."""
        # mrlint: disable=knob-bypass
        import os

        def a():
            return os.environ.get("MRTPU_HEADER_TEST", "1")
    '''
    findings, live = run_fixture(str(tmp_path), {"mod.py": src},
                                 ["knob-registry"],
                                 docs={"settings.md":
                                       "| `MRTPU_HEADER_TEST` | 1 | x |"})
    assert [f.rule for f in live] == []
    assert any(f.suppressed and f.rule == "knob-bypass" for f in findings)


def test_changed_scope_keeps_reconciliation_findings(tmp_path):
    # a doc-only edit can orphan a metric/knob registered in an
    # UNCHANGED code file; the quick gate's changed-file report scope
    # must still surface those whole-tree invariants
    files = {"mod.py": 'NAME = "mrtpu_orphan_total"\n'}
    for rel, src in files.items():
        path = os.path.join(str(tmp_path), "pkg", rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(src)
    os.makedirs(os.path.join(str(tmp_path), "doc"), exist_ok=True)
    with open(os.path.join(str(tmp_path), "doc", "observability.md"),
              "w") as f:
        f.write("no catalog entry here\n")
    project = lint.Project(str(tmp_path), package="pkg")
    # report scope excludes mod.py entirely — the finding must survive
    scoped = lint.run(project, rules=["metric-catalog"],
                      only_paths={"doc/observability.md"})
    assert any(f.rule == "metric-undocumented" and not f.suppressed
               for f in scoped)
    assert all(f.symbol == "mrtpu_orphan_total" for f in scoped)


def test_baseline_suppression(tmp_path):
    _, live = run_fixture(str(tmp_path), {"mod.py": LOCK_BAD_MUTATION},
                          ["lock-discipline"])
    baseline = {f.fingerprint for f in live}
    project = lint.Project(str(tmp_path), package="pkg")
    again = lint.run(project, rules=["lock-discipline"],
                     baseline=baseline)
    assert all(f.suppressed for f in again)


# ---------------------------------------------------------------------------
# self-check: the shipped package is clean AND coverage is real
# ---------------------------------------------------------------------------

def test_selfcheck_repo_runs_clean():
    """ISSUE 11 acceptance: zero unsuppressed findings on the tree."""
    project = lint.Project(
        REPO, extra_files=("scripts/mrctl.py", "scripts/mrlaunch.py"))
    findings = lint.run(project)
    live = [f for f in findings if not f.suppressed]
    assert live == [], "\n" + "\n".join(str(f) for f in live)
    # the pragma pile must stay visible and bounded: every suppression
    # is a reviewed, justified exception (doc/lint.md policy)
    assert sum(1 for f in findings if f.suppressed) < 40


def test_selfcheck_coverage_floor():
    """Zero findings must not mean zero analysis: the purity checker
    has to see a substantial traced set or entry detection regressed."""
    project = lint.Project(REPO)
    graph = CallGraph(project)
    entries = _purity._entries(graph)
    traced = graph.reachable(entries)
    assert len(graph.funcs) > 800
    assert len(entries) > 25, "jit/shard_map entry detection regressed"
    assert len(traced) > 80
    mods = {t.module.relpath for t in traced}
    for must in ("gpu_mapreduce_tpu/parallel/shuffle.py",
                 "gpu_mapreduce_tpu/parallel/wire.py",
                 "gpu_mapreduce_tpu/plan/fuser.py"):
        assert must in mods, f"{must} fell out of the traced set"


def test_cli_json_and_exit_code():
    """The CLI contract ci.sh relies on: exit 0 + parseable --json on a
    clean tree, without importing jax (SIGALRM-free, fast)."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mrlint.py"),
         "--json", "-"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    payload = json.loads(res.stdout)
    assert payload["total"] == 0
    assert payload["files_scanned"] > 100
    assert "jax" not in res.stderr.lower()


def test_cli_unknown_rule_exits_2():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mrlint.py"),
         "-r", "no-such-rule"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 2
    assert "unknown rule" in res.stderr


# ---------------------------------------------------------------------------
# net-timeout
# ---------------------------------------------------------------------------

NET_BAD = """
    import socket
    import urllib.request

    def probe(port):
        socket.create_connection(("127.0.0.1", port)).close()

    def fetch(url):
        return urllib.request.urlopen(url).read()

    def legacy(host):
        import http.client
        return http.client.HTTPConnection(host, 80)
"""

NET_CLEAN = """
    import socket
    import urllib.request

    def probe(port):
        socket.create_connection(("127.0.0.1", port),
                                 timeout=0.5).close()

    def fetch(url):
        return urllib.request.urlopen(url, timeout=30.0).read()

    def fetch_positional(url):
        # timeout in its positional slot counts too
        return urllib.request.urlopen(url, None, 30.0).read()

    def legacy(host):
        import http.client
        return http.client.HTTPConnection(host, 80, 10.0)

    def intentional(port):
        socket.create_connection(("127.0.0.1", port)).close()  # mrlint: disable=net-timeout
"""


def test_net_timeout_true_positive(tmp_path):
    _, live = run_fixture(str(tmp_path),
                          {"serve/mod.py": NET_BAD},
                          rules=["net-timeout"])
    assert len(live) == 3
    assert all(f.rule == "net-timeout" for f in live)


def test_net_timeout_clean(tmp_path):
    _, live = run_fixture(str(tmp_path),
                          {"serve/mod.py": NET_CLEAN},
                          rules=["net-timeout"])
    assert live == []


def test_net_timeout_out_of_scope_module_ignored(tmp_path):
    # the rule scopes to serve/ + obs/httpd.py + opted-in extras: a
    # data-plane module with a raw socket is not this rule's business
    _, live = run_fixture(str(tmp_path),
                          {"parallel/mod.py": NET_BAD},
                          rules=["net-timeout"])
    assert live == []


def test_net_timeout_tree_is_clean():
    project = lint.Project(REPO, package="gpu_mapreduce_tpu",
                           extra_files=("scripts/mrctl.py",
                                        "scripts/mrlaunch.py"))
    live = [f for f in lint.run(project, rules=["net-timeout"])
            if not f.suppressed]
    assert live == [], [str(f) for f in live]
