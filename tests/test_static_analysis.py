"""Tier-1 gate for the invariant analyzers (kubernetes_tpu.analysis).

Two jobs:

  * the shipped tree must analyze CLEAN — a regression in lock
    discipline, plugin purity, or jit-boundary hygiene fails CI here,
    the pytest analogue of wiring `go vet`/`-race` into the build;
  * each checker must actually CATCH its seeded-violation fixture and
    stay silent on the negative fixture — the analyzer is itself code,
    and a checker that silently stopped firing is worse than none.
"""

import os

import pytest

from kubernetes_tpu.analysis import default_targets, run_analysis
from kubernetes_tpu.analysis.__main__ import main as cli_main
from kubernetes_tpu.analysis.core import (
    ALL_RULES,
    RULE_BARE_SUPPRESSION,
    RULE_CLAMP,
    RULE_D2H,
    RULE_DONATION,
    RULE_DTYPE,
    RULE_JIT,
    RULE_LOCK,
    RULE_PURITY,
    RULE_RETRACE,
    RULE_SHAPE,
    RULE_SHARD,
    RULE_BREAKER,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")

CHECKER_KEYS = (
    "locks", "purity", "jit", "d2h", "donation", "clamp", "retrace",
    "shape", "dtype", "shard", "breaker",
)


def fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


def analyze_fixture(name: str):
    path = fixture(name)
    return run_analysis({key: [path] for key in CHECKER_KEYS})


def analyze_paths(**overrides):
    """Run with every checker EMPTY except the given keys — keeps the
    suppression unit tests off the shipped tree."""
    targets = {key: [] for key in CHECKER_KEYS}
    targets.update({k: list(v) for k, v in overrides.items()})
    return run_analysis(targets)


def marked_lines(name: str):
    """1-based lines carrying a '# VIOLATION' marker in the fixture."""
    with open(fixture(name), "r", encoding="utf-8") as f:
        return {
            i
            for i, line in enumerate(f.read().splitlines(), start=1)
            if "VIOLATION" in line
        }


# ----- the shipped tree ------------------------------------------------------


def test_shipped_tree_is_clean():
    findings = run_analysis()
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_default_targets_exist_and_are_nontrivial():
    t = default_targets()
    for key in CHECKER_KEYS:
        assert t[key], key
        for p in t[key]:
            assert os.path.exists(p), p


def test_cli_exits_zero_on_tree(capsys):
    assert cli_main([]) == 0
    assert "no findings" in capsys.readouterr().out


def test_cli_exits_nonzero_on_findings(capsys):
    assert cli_main([fixture("lock_bad.py")]) == 1
    out = capsys.readouterr().out
    assert RULE_LOCK in out


def test_cli_json_report(capsys):
    import json

    assert cli_main(["--json", fixture("jit_bad.py")]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["count"] == len(report["findings"]) > 0
    assert report["by_rule"].get(RULE_JIT) == report["count"]
    f0 = report["findings"][0]
    assert {"rule", "path", "line", "message"} <= set(f0)


def test_cli_rule_filter(capsys):
    # lock_bad has only lock findings — filtering to jit-boundary shows none
    # but the exit code still reflects the unfiltered run
    assert cli_main(["--rule", RULE_JIT, fixture("jit_bad.py")]) == 1
    assert cli_main(["--rule", RULE_LOCK, fixture("lock_good.py")]) == 0
    capsys.readouterr()


def test_cli_rule_filter_new_rules(capsys):
    assert cli_main(["--rule", RULE_D2H, fixture("d2h_bad.py")]) == 1
    out = capsys.readouterr().out
    assert RULE_D2H in out
    assert cli_main(["--rule", RULE_CLAMP, fixture("clamp_bad.py")]) == 1
    capsys.readouterr()


def test_cli_help_lists_all_rules(capsys):
    # `--rule` must advertise every rule, the new families included —
    # the CLI is the discovery surface for the suppression names
    with pytest.raises(SystemExit) as e:
        cli_main(["--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule in out, rule


# ----- per-checker fixtures --------------------------------------------------


@pytest.mark.parametrize(
    "name,rule",
    [
        ("lock_bad.py", RULE_LOCK),
        ("purity_bad.py", RULE_PURITY),
        ("jit_bad.py", RULE_JIT),
        ("d2h_bad.py", RULE_D2H),
        ("donation_bad.py", RULE_DONATION),
        ("clamp_bad.py", RULE_CLAMP),
        ("retrace_bad.py", RULE_RETRACE),
        ("shape_bad.py", RULE_SHAPE),
        ("dtype_bad.py", RULE_DTYPE),
        ("shard_bad.py", RULE_SHARD),
        ("breaker_bad.py", RULE_BREAKER),
    ],
)
def test_positive_fixture_caught(name, rule):
    findings = analyze_fixture(name)
    assert findings, f"{name}: seeded violations not detected"
    assert {f.rule for f in findings} == {rule}
    found_lines = {f.line for f in findings}
    missing = marked_lines(name) - found_lines
    assert not missing, f"{name}: VIOLATION-marked lines not found: {missing}"


@pytest.mark.parametrize(
    "name",
    [
        "lock_good.py",
        "purity_good.py",
        "jit_good.py",
        "d2h_good.py",
        "donation_good.py",
        "clamp_good.py",
        "retrace_good.py",
        "shape_good.py",
        "dtype_good.py",
        "shard_good.py",
        "breaker_good.py",
    ],
)
def test_negative_fixture_silent(name):
    findings = analyze_fixture(name)
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


# ----- suppressions ----------------------------------------------------------


def test_justified_suppression_silences(tmp_path):
    src = (
        "import threading\n"
        '_KTPU_GUARDED = {"Owner": {"lock": "_mu", "guards": {"cache": None}}}\n'
        "class Owner:\n"
        "    def poke(self):\n"
        "        # ktpu: allow(lock-discipline) — single-threaded bootstrap\n"
        "        self.cache.put(1, 2)\n"
    )
    p = tmp_path / "suppressed.py"
    p.write_text(src)
    findings = analyze_paths(locks=[str(p)])
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_trailing_suppression_silences(tmp_path):
    src = (
        "import threading\n"
        '_KTPU_GUARDED = {"Owner": {"lock": "_mu", "guards": {"cache": None}}}\n'
        "class Owner:\n"
        "    def poke(self):\n"
        "        self.cache.put(1, 2)  # ktpu: allow(lock-discipline) -- boot\n"
    )
    p = tmp_path / "trailing.py"
    p.write_text(src)
    findings = analyze_paths(locks=[str(p)])
    assert findings == []


def test_stacked_suppressions_all_attach(tmp_path):
    # two standalone comments (one per rule, each with its own reason)
    # above one statement must BOTH cover it — the natural way to silence
    # two rules without cramming two reasons into one line
    src = (
        "import threading\n"
        '_KTPU_GUARDED = {"Owner": {"lock": "_mu", "guards": {"cache": None}}}\n'
        "class Owner:\n"
        "    def poke(self):\n"
        "        # ktpu: allow(jit-boundary) — not actually jit code\n"
        "        # ktpu: allow(lock-discipline) — single-threaded bootstrap\n"
        "        self.cache.put(1, 2)\n"
    )
    p = tmp_path / "stacked.py"
    p.write_text(src)
    findings = analyze_paths(locks=[str(p)])
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


def test_bare_suppression_is_itself_a_finding(tmp_path):
    src = (
        "import threading\n"
        '_KTPU_GUARDED = {"Owner": {"lock": "_mu", "guards": {"cache": None}}}\n'
        "class Owner:\n"
        "    def poke(self):\n"
        "        self.cache.put(1, 2)  # ktpu: allow(lock-discipline)\n"
    )
    p = tmp_path / "bare.py"
    p.write_text(src)
    findings = analyze_paths(locks=[str(p)])
    rules = {f.rule for f in findings}
    # the reasonless comment does NOT silence, and is flagged itself
    assert rules == {RULE_LOCK, RULE_BARE_SUPPRESSION}


def test_wrong_rule_suppression_does_not_silence(tmp_path):
    src = (
        "import threading\n"
        '_KTPU_GUARDED = {"Owner": {"lock": "_mu", "guards": {"cache": None}}}\n'
        "class Owner:\n"
        "    def poke(self):\n"
        "        # ktpu: allow(jit-boundary) — wrong rule entirely\n"
        "        self.cache.put(1, 2)\n"
    )
    p = tmp_path / "wrong.py"
    p.write_text(src)
    findings = analyze_paths(locks=[str(p)])
    assert {f.rule for f in findings} == {RULE_LOCK}


def test_donation_loop_and_with_targets_revive(tmp_path):
    # rebinding a donated name via a for-loop target or `with ... as`
    # revives it — only the read BEFORE the rebinding is a violation
    src = (
        "import functools\n"
        "import jax\n"
        "\n"
        "@functools.partial(jax.jit, donate_argnames=('used',))\n"
        "def commit(used, delta):\n"
        "    return used + delta\n"
        "\n"
        "def loops(used, delta, runs, cm):\n"
        "    out = commit(used, delta)\n"
        "    for used in runs:\n"
        "        out = out + used  # rebound by the loop target: fine\n"
        "    with cm() as used:\n"
        "        out = out + used  # rebound by `as`: fine\n"
        "    return out\n"
    )
    p = tmp_path / "revive.py"
    p.write_text(src)
    findings = analyze_paths(donation=[str(p)])
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)

    # control: without the rebindings the same reads ARE violations
    bad = src.replace("for used in runs:", "for other in runs:").replace(
        "as used:", "as other:"
    )
    p2 = tmp_path / "no_revive.py"
    p2.write_text(bad)
    findings = analyze_paths(donation=[str(p2)])
    assert len(findings) == 2, "\n".join(f.format() for f in findings)
    assert {f.rule for f in findings} == {RULE_DONATION}


def test_d2h_with_header_fetch_caught(tmp_path):
    # withitem nodes are not exprs — a blocking fetch hiding in a `with`
    # context header must still be scanned
    src = (
        "def harvest(span, count_dev):\n"
        "    with span(int(count_dev)):\n"
        "        return 1\n"
    )
    p = tmp_path / "withhdr.py"
    p.write_text(src)
    findings = analyze_paths(d2h=[str(p)])
    assert len(findings) == 1 and findings[0].rule == RULE_D2H, findings


def test_same_basename_modules_do_not_cross_resolve(tmp_path):
    # ops/explain.py and observability/explain.py share a basename: a
    # host module must not resolve ANOTHER module's jit roots through its
    # own bare names (path-scoped self tables)
    d1 = tmp_path / "ops"
    d2 = tmp_path / "obs"
    d1.mkdir()
    d2.mkdir()
    (d1 / "explain.py").write_text(
        "import jax\n\n@jax.jit\ndef kernel(x):\n    return x\n"
    )
    (d2 / "explain.py").write_text(
        "import numpy as np\n"
        "def kernel():\n"
        "    return [1, 2]\n"
        "def host():\n"
        "    return np.asarray(kernel())  # local host fn, same name\n"
    )
    findings = analyze_paths(
        jit=[str(d1 / "explain.py")], d2h=[str(d2 / "explain.py")]
    )
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)


# ----- runtime sanitizer -----------------------------------------------------


@pytest.fixture
def sanitize_on(monkeypatch):
    from kubernetes_tpu.analysis import sanitizer

    monkeypatch.setenv("KTPU_SANITIZE", "1")
    sanitizer.reset_enabled_memo()
    yield sanitizer
    monkeypatch.delenv("KTPU_SANITIZE", raising=False)
    sanitizer.reset_enabled_memo()


def test_assert_owned_raises_without_lock(sanitize_on):
    import threading

    lock = threading.RLock()
    before = sanitize_on.violation_count()
    with pytest.raises(AssertionError, match="ktpu-sanitize\\[lock\\]"):
        sanitize_on.assert_owned(lock, "test site")
    assert sanitize_on.violation_count() == before + 1
    with lock:
        sanitize_on.assert_owned(lock, "test site")  # held → silent
    sanitize_on.assert_owned(None, "no discipline")  # standalone → silent


def test_assert_owned_noop_when_disabled(monkeypatch):
    import threading

    from kubernetes_tpu.analysis import sanitizer

    monkeypatch.delenv("KTPU_SANITIZE", raising=False)
    sanitizer.reset_enabled_memo()
    sanitizer.assert_owned(threading.RLock(), "disabled")  # must not raise


def test_sanitizer_counter_registration(sanitize_on):
    from kubernetes_tpu.metrics import SchedulerMetrics

    prom = SchedulerMetrics()
    sanitize_on.register_counter(prom.sanitizer_violations)
    try:
        import threading

        with pytest.raises(AssertionError):
            sanitize_on.assert_owned(threading.RLock(), "counter probe")
        assert prom.sanitizer_violations.value(kind="lock") == 1.0
        assert (
            "scheduler_tpu_sanitizer_violations_total" in prom.registry.expose()
        )
    finally:
        sanitize_on._counters.remove(prom.sanitizer_violations)


def test_mirror_consistency_detects_seeded_drift(sanitize_on):
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.cache.cache import Cache
    from kubernetes_tpu.cache.mirror import SnapshotMirror

    cache = Cache()
    cache.add_node(
        Node(name="n0", capacity=Resource.from_map({"cpu": "8", "memory": "8Gi"}))
    )
    pod = Pod(
        name="p0",
        containers=[Container(requests={"cpu": "1", "memory": "1Gi"})],
    )
    cache.assume_pod(pod, "n0")
    mirror = SnapshotMirror()
    mirror.update(cache)
    sanitize_on.check_mirror_consistency(cache, mirror)  # in sync → silent

    # seed drift the generation watermark can't see: a usage row corrupted
    # behind the mirror's back (the bug class a broken fast committer makes)
    mirror.nodes.num_pods[0] += 1
    with pytest.raises(AssertionError, match="ktpu-sanitize\\[mirror\\]"):
        sanitize_on.check_mirror_consistency(cache, mirror)


def test_cache_bulk_assume_probe_trips_without_lock(sanitize_on):
    import threading

    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.cache import cache as cache_mod

    cache = cache_mod.Cache()
    cache.add_node(
        Node(name="n0", capacity=Resource.from_map({"cpu": "8", "memory": "8Gi"}))
    )
    pod = Pod(
        name="p0",
        uid="u0",
        containers=[Container(requests={"cpu": "1", "memory": "1Gi"})],
    )
    lock = threading.RLock()
    cache._ktpu_lock = lock  # what Scheduler.__init__ stamps under sanitize
    with pytest.raises(AssertionError, match="assume_pods_bulk"):
        cache.assume_pods_bulk([(pod, "n0")])
    with lock:
        out = cache.assume_pods_bulk([(pod, "n0")])
    assert not isinstance(out[0], str)


def test_mirror_consistency_noop_when_disabled(monkeypatch):
    from kubernetes_tpu.analysis import sanitizer

    monkeypatch.delenv("KTPU_SANITIZE", raising=False)
    sanitizer.reset_enabled_memo()
    sanitizer.check_mirror_consistency(None, None)  # gated off → no touch


# ----- retrace hook (jit recompile accounting) -------------------------------


@pytest.fixture
def retrace_armed(sanitize_on):
    yield sanitize_on
    sanitize_on.reset_retrace()


def test_retrace_hook_counts_post_warm_recompiles(retrace_armed):
    import jax
    import jax.numpy as jnp

    san = retrace_armed

    @jax.jit
    def toy(x):
        return x + 1

    toy(jnp.ones(3))  # warmup compile
    san.mark_jit_warm()
    san.register_jit_root("test.toy", toy)
    assert san.unexpected_recompiles() == {}
    toy(jnp.ones(3))  # warm signature — cache hit
    assert san.unexpected_recompiles() == {}
    toy(jnp.ones(5))  # new shape → unexpected recompile
    toy(jnp.ones(7))
    got = san.unexpected_recompiles()
    assert got.get("test.toy") == 2, got


def test_retrace_counter_lands_in_metrics(retrace_armed):
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.metrics import SchedulerMetrics

    san = retrace_armed
    prom = SchedulerMetrics()
    san.register_recompile_counter(prom.jit_recompiles)

    @jax.jit
    def toy2(x):
        return x * 3

    toy2(jnp.ones(3))
    san.mark_jit_warm()
    san.register_jit_root("test.toy2", toy2)
    toy2(jnp.ones(9))  # post-warm recompile
    assert san.unexpected_recompiles().get("test.toy2") == 1
    try:
        assert prom.jit_recompiles.value(fn="test.toy2") == 1.0
        assert "scheduler_tpu_jit_recompiles_total" in prom.registry.expose()
    finally:
        san._recompile_counters.discard(prom.jit_recompiles)


def test_retrace_discovers_shipped_roots(retrace_armed):
    san = retrace_armed
    roots = san._discover_jit_roots()
    for want in (
        "fastpath.sig_scan",
        "resident.resident_run",
        "chain.chain_dispatch",
        "gang.gang_run",
        "wave.wave_run",
    ):
        assert want in roots, sorted(roots)


def test_retrace_empty_before_warm_mark(retrace_armed):
    assert retrace_armed.unexpected_recompiles() == {}


# ----- warm config0 drain: zero unexpected recompiles ------------------------


def _recompile_nodes(n):
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node

    return [
        Node(
            name=f"node-{i}",
            labels={
                "kubernetes.io/hostname": f"node-{i}",
                "topology.kubernetes.io/zone": f"z{i % 3}",
            },
            capacity=Resource.from_map(
                {"cpu": "16", "memory": "64Gi", "pods": 64}
            ),
        )
        for i in range(n)
    ]


def _recompile_pods(n, tag):
    """Mixed workload: signature pods (resident/fast path) + topology-
    spread pods (wave/chain path) — same SHAPES for every `tag`."""
    from kubernetes_tpu.api.types import (
        Container,
        LabelSelector,
        Pod,
        TopologySpreadConstraint,
    )

    pods = []
    for i in range(n):
        app = f"a{i % 4}"
        spread = ()
        # segregate: the first 2/3 are plain signature pods (resident /
        # fast path batches), the last 1/3 carry a spread term (wave /
        # chain path) — interleaving them would put a cross-pod term in
        # EVERY batch and route the whole drain through the wave path
        if i >= (2 * n) // 3:
            spread = (
                TopologySpreadConstraint(
                    max_skew=5,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": app}),
                ),
            )
        pods.append(
            Pod(
                name=f"{tag}-p{i}",
                labels={"app": app},
                topology_spread_constraints=spread,
                containers=[
                    Container(
                        name="c",
                        requests={
                            "cpu": ["100m", "250m"][i % 2],
                            "memory": "64Mi",
                        },
                    )
                ],
            )
        )
    return pods


def _recompile_drain(nodes, pods):
    from kubernetes_tpu.framework import config as cfg
    from kubernetes_tpu.scheduler import Scheduler

    conf = cfg.SchedulerConfiguration(
        batch_size=64,
        fast_device_min=32,
        resident_run_max=256,
        resident_window=32,
    )
    s = Scheduler(configuration=conf)
    s.binding_sink = lambda pod, node: None
    for n in nodes:
        s.on_node_add(n)
    for p in pods:
        s.on_pod_add(p)
    s.schedule_pending()
    return s


def test_warm_config0_drain_zero_unexpected_recompiles(retrace_armed):
    """Satellite gate: after a warmup drain compiled every shape the
    steady state needs, a second same-shaped drain must hit the jit
    caches exactly — 0 unexpected recompiles across the resident, wave/
    chain, and fast paths (KTPU_SANITIZE=1 retrace hook)."""
    san = retrace_armed
    nodes = _recompile_nodes(16)
    warm = _recompile_drain(nodes, _recompile_pods(192, "warm"))
    mix_keys = ("resident_batches", "fast_batches", "wave_batches",
                "chain_batches")
    warm_mix = {k: warm.metrics.get(k, 0) for k in mix_keys}
    san.mark_jit_warm()

    steady = _recompile_drain(nodes, _recompile_pods(192, "steady"))
    got = san.unexpected_recompiles()
    assert got == {}, f"unexpected recompiles in a warm drain: {got}"
    # the run must actually have exercised the paths the gate claims:
    # resident (signature feed), wave or chain (spread terms), and the
    # fast committer path
    mix = {k: steady.metrics.get(k, 0) for k in mix_keys}
    assert mix["resident_batches"] > 0 or warm_mix["resident_batches"] > 0, (
        mix,
        warm_mix,
    )
    assert (
        mix["wave_batches"] + mix["chain_batches"] > 0
        or warm_mix["wave_batches"] + warm_mix["chain_batches"] > 0
    ), (mix, warm_mix)
    assert mix["fast_batches"] > 0 or warm_mix["fast_batches"] > 0, (
        mix,
        warm_mix,
    )


# ----- symbolic shape interpreter (shape / dtype / shard) --------------------


def test_shape_engine_infers_root_returns():
    """The interpreter must produce CONCRETE summaries for the major
    roots — an all-Unknown inference would make the zero-findings gate
    vacuous and the eval_shape cross-check a no-op."""
    from kubernetes_tpu.analysis import SHAPE_MODULES, _PKG_ROOT
    from kubernetes_tpu.analysis.core import load_source
    from kubernetes_tpu.analysis.shape import Arr, ShapeEngine, TupV, dim_of_sym

    mods = [load_source(os.path.join(_PKG_ROOT, p)) for p in SHAPE_MODULES]
    eng = ShapeEngine().run(mods)
    P = dim_of_sym("P")
    N = dim_of_sym("N")

    def leading(key, idx):
        v = eng.root_returns[key]
        assert isinstance(v, TupV), (key, v)
        el = v.items[idx]
        assert isinstance(el, Arr) and el.shape is not None, (key, el)
        return el.shape

    assert leading("gang.gang_schedule", 0) == (P,)
    assert leading("gang.gang_schedule", 2) == (P, 9)  # reason_counts
    assert leading("wave.wave_schedule", 4) == (3, P)  # wave stats
    assert leading("resident.resident_run", 0) == (P,)
    assert leading("fastpath.sig_scan", 0) == (P,)
    stack = eng.root_returns["explain.explain_masks"].items[0]
    assert stack.shape == (9, P, N)


def test_shape_engine_every_ops_root_annotated():
    """Acceptance gate: every jit root in ops/ (and the device-mirror
    splicer) carries an axes annotation — enforced by the shape rule, so
    the tree-is-clean test covers it; this asserts the roster of roots
    itself so a silently-unDISCOVERED root would also fail."""
    from kubernetes_tpu.analysis import SHAPE_MODULES, _PKG_ROOT
    from kubernetes_tpu.analysis.core import load_source
    from kubernetes_tpu.analysis.shape import ShapeEngine

    mods = [load_source(os.path.join(_PKG_ROOT, p)) for p in SHAPE_MODULES]
    eng = ShapeEngine().run(mods)
    annotated = {f"{rec.base}.{rec.qual}" for rec, _ann in eng.roots}
    for want in (
        "fastpath.static_eval",
        "fastpath.sig_scan",
        "gang.gang_schedule",
        "gang.gang_run",
        "wave.wave_schedule",
        "wave.wave_run",
        "chain.chain_dispatch",
        "resident.resident_run",
        "explain.explain_masks",
        "preemption.narrow_candidates",
        "pipeline._pipeline",
        "wire._unpacker.run",
        "device_mirror._delta_applier.apply",
    ):
        assert want in annotated, sorted(annotated)


def test_shape_rule_rosters_document_reasons():
    """Every _KTPU_N_COLLECTIVES entry must carry a non-empty reason —
    the roster is the multichip refactor's collective inventory, not an
    escape hatch."""
    from kubernetes_tpu.analysis import SHAPE_MODULES, _PKG_ROOT
    from kubernetes_tpu.analysis.core import load_source
    from kubernetes_tpu.analysis.shape import ShapeEngine

    mods = [load_source(os.path.join(_PKG_ROOT, p)) for p in SHAPE_MODULES]
    eng = ShapeEngine().run(mods)
    total = 0
    for mi in eng.mods.values():
        for fn, reason in mi.roster.items():
            assert reason.strip(), (mi.base, fn)
            # a rostered name must resolve to a real function (typo guard)
            assert fn in mi.funcs, (mi.base, fn, sorted(mi.funcs))
            total += 1
    assert total >= 10, total


def test_shard_rosters_are_a_burn_down():
    """ISSUE 14 acceptance: every sharded-path roster entry carries an
    explicit ``resolved(<mechanism>): ...`` sharding story, parsed by
    collective_roster().  A new N-crossing can only land (a) unrostered —
    the shard rule flags it, tree-is-clean fails — or (b) rostered but
    unresolved — the engine flags the entry itself AND this test names
    it.  The worklist cannot silently regress."""
    from kubernetes_tpu.analysis import (
        SHAPE_MODULES,
        _PKG_ROOT,
        collective_roster,
    )
    from kubernetes_tpu.analysis.core import load_source

    mods = [load_source(os.path.join(_PKG_ROOT, p)) for p in SHAPE_MODULES]
    roster = collective_roster(mods)
    unresolved = [
        (path, qual)
        for path, entries in roster.items()
        for qual, e in entries.items()
        if not e["resolved"]
    ]
    assert unresolved == [], unresolved
    mechanisms = {
        e["mechanism"] for entries in roster.values() for e in entries.values()
    }
    assert mechanisms <= {"collective", "local", "replicated"}, mechanisms
    total = sum(len(entries) for entries in roster.values())
    assert total >= 20, total  # the inventoried worklist, fully resolved


def test_unresolved_roster_entry_is_flagged(tmp_path):
    """A rostered-but-unresolved entry is itself a shard finding anchored
    to the entry's line, and a reasoned suppression can park it."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        '_KTPU_N_COLLECTIVES = {\n'
        '    "f": "reduces over N, story TBD",\n'
        "}\n"
        "# ktpu: axes(x=i64[T,N])\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return jnp.sum(x, axis=1)\n"
    )
    p = tmp_path / "unresolved_mod.py"
    p.write_text(src)
    findings = run_analysis({k: [str(p)] for k in CHECKER_KEYS})
    shard = [f for f in findings if f.rule == RULE_SHARD]
    assert len(shard) == 1, [f.format() for f in findings]
    assert shard[0].line == 4
    assert "resolved(collective|local|replicated)" in shard[0].message
    # the same entry with a story is clean
    fixed = src.replace(
        '"reduces over N, story TBD"',
        '"resolved(collective): per-shard partial sums + psum"',
    )
    p.write_text(fixed)
    findings = run_analysis({k: [str(p)] for k in CHECKER_KEYS})
    assert [f for f in findings if f.rule == RULE_SHARD] == []


# ----- eval_shape cross-check (runtime complement) ---------------------------


def test_shapecheck_tree_is_clean():
    from kubernetes_tpu.analysis import shapecheck

    res = shapecheck.cross_check()
    assert res == {}, res


def test_shapecheck_skips_are_reasoned():
    from kubernetes_tpu.analysis import shapecheck

    skips = shapecheck.skipped()
    assert set(skips) == {
        "chain.chain_dispatch",
        "wire._unpacker.run",
        "device_mirror._delta_applier.apply",
    }, skips
    assert all(reason.strip() for reason in skips.values())


def test_shapecheck_randomized_sizes_property():
    """Property: the interpreter and jax.eval_shape agree on every
    instantiable ops/ root across randomized distinct axis sizes —
    transposed or mislabeled dims cannot hide behind coincident sizes."""
    import random

    from kubernetes_tpu.analysis import shapecheck

    rng = random.Random(0xC0FFEE)
    for _ in range(2):
        axes = ["P", "N", "S", "C", "A", "G", "Tsp", "Tip", "E", "M"]
        pool = rng.sample(range(2, 23), len(axes))
        sizes = dict(zip(axes, pool))
        sizes["Rn"] = rng.randint(3, 6)
        sizes["Rp"] = sizes["Rn"] + rng.randint(0, 2)
        res = shapecheck.cross_check(sizes=sizes)
        assert res == {}, (sizes, res)


@pytest.mark.slow
def test_shapecheck_randomized_sizes_property_deep():
    import random

    from kubernetes_tpu.analysis import shapecheck

    rng = random.Random(7)
    for _ in range(5):
        sizes = {
            k: rng.randint(2, 31)
            for k in ("P", "N", "S", "C", "A", "G", "Tsp", "Tip", "E", "M",
                      "K", "V", "NS", "TA", "TL", "U", "UP", "IMG", "IP",
                      "NT", "PT", "Kd", "Kd2")
        }
        sizes["Rn"] = rng.randint(3, 8)
        sizes["Rp"] = sizes["Rn"] + rng.randint(0, 3)
        res = shapecheck.cross_check(sizes=sizes)
        assert res == {}, (sizes, res)


def test_shapecheck_detects_seeded_annotation_drift(tmp_path):
    """A root whose axes annotation no longer matches its code must
    surface as a cross-check mismatch (the anti-rot guarantee)."""
    from kubernetes_tpu.analysis import shapecheck
    from kubernetes_tpu.analysis.core import SourceModule

    # the annotation CLAIMS the output keeps [P, N]; the kernel transposes
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "# ktpu: axes(x=i64[P,N])\n"
        "@jax.jit\n"
        "def transposer(x):\n"
        "    return jnp.zeros((x.shape[0], x.shape[1]), jnp.int64).T\n"
    )
    p = tmp_path / "drifty.py"
    p.write_text(src)
    import sys

    sys.path.insert(0, str(tmp_path))
    try:
        mods = [SourceModule.load(str(p))]
        # engine infers [N, P]; eval_shape also gives [N, P] — clean
        res = shapecheck.cross_check(mods=mods)
        assert res == {}, res
    finally:
        sys.path.pop(0)

    # now a file whose INFERRED shape disagrees with trace: the engine
    # is fed a stale copy of the source while jax traces the new one
    stale = src.replace(".T\n", "\n")  # stale inference: [P, N]
    p2 = tmp_path / "drifty2.py"
    p2.write_text(src.replace("transposer", "transposer2"))
    import importlib

    sys.path.insert(0, str(tmp_path))
    try:
        importlib.import_module("drifty2")
        mods = [SourceModule(str(p2), stale.replace("transposer",
                                                    "transposer2"))]
        res = shapecheck.cross_check(mods=mods)
        assert "drifty2.transposer2" in res, res
        assert any("axis" in m for m in res["drifty2.transposer2"]), res
    finally:
        sys.path.pop(0)


def test_sanitizer_shape_check_memoized_and_counts(sanitize_on, monkeypatch):
    from kubernetes_tpu.analysis import sanitizer, shapecheck
    from kubernetes_tpu.metrics import SchedulerMetrics

    sanitizer.reset_shape_check()
    calls = []

    def fake_cross_check(sizes=None, mods=None):
        calls.append(1)
        return {"ops.fake_root": ["axis 0 inferred N=7, traced 5"]}

    monkeypatch.setattr(shapecheck, "cross_check", fake_cross_check)
    prom = SchedulerMetrics()
    sanitizer.register_shape_counter(prom.shape_check_failures)
    try:
        got = sanitizer.check_root_shapes()
        assert got == {"ops.fake_root": ["axis 0 inferred N=7, traced 5"]}
        assert prom.shape_check_failures.value(fn="ops.fake_root") == 1.0
        assert (
            "scheduler_tpu_shape_check_failures_total"
            in prom.registry.expose()
        )
        # memoized: a second drain neither re-runs nor double-counts
        sanitizer.check_root_shapes()
        assert len(calls) == 1
        assert prom.shape_check_failures.value(fn="ops.fake_root") == 1.0
    finally:
        sanitizer._shape_counters.discard(prom.shape_check_failures)
        sanitizer.reset_shape_check()


def test_sanitizer_shape_check_noop_when_disabled(monkeypatch):
    from kubernetes_tpu.analysis import sanitizer

    monkeypatch.delenv("KTPU_SANITIZE", raising=False)
    sanitizer.reset_enabled_memo()
    sanitizer.reset_shape_check()
    assert sanitizer.check_root_shapes() == {}


def test_warm_drain_shape_check_zero_mismatches(sanitize_on):
    """Acceptance gate: a config0-shaped drain under KTPU_SANITIZE=1 runs
    the eval_shape cross-check and reports ZERO mismatches (wired through
    Scheduler → sanitizer.check_root_shapes at drain end)."""
    from kubernetes_tpu.analysis import sanitizer
    from kubernetes_tpu.metrics import SchedulerMetrics

    sanitizer.reset_shape_check()
    try:
        s = _recompile_drain(_recompile_nodes(8), _recompile_pods(48, "sc"))
        res = sanitizer.check_root_shapes()
        assert res == {}, res
        # the drain itself must have armed the check (memo populated)
        assert sanitizer._shape_check_result == {}
        expo = s.prom.registry.expose()
        assert "scheduler_tpu_shape_check_failures_total" in expo
    finally:
        sanitizer.reset_shape_check()


# ----- baseline workflow -----------------------------------------------------


def test_baseline_roundtrip_suppresses_known_findings(tmp_path, capsys):
    base = tmp_path / "baseline.json"
    bad = fixture("shape_bad.py")
    assert cli_main(["--write-baseline", str(base), bad]) == 0
    capsys.readouterr()
    # with the baseline, the same dirty file now exits clean
    assert cli_main(["--baseline", str(base), bad]) == 0
    out = capsys.readouterr().out
    assert "no findings" in out
    assert "baselined" in out


def test_baseline_fails_on_new_findings(tmp_path, capsys):
    import shutil

    base = tmp_path / "baseline.json"
    work = tmp_path / "work.py"
    shutil.copy(fixture("dtype_bad.py"), work)
    assert cli_main(["--write-baseline", str(base), str(work)]) == 0
    # introduce a NEW finding on top of the baselined ones
    src = work.read_text()
    src += (
        "\n\n# ktpu: axes(z=i64[P,N])\n"
        "@jax.jit\n"
        "def fresh(z):\n"
        "    return z / 4\n"
    )
    work.write_text(src)
    capsys.readouterr()
    assert cli_main(["--baseline", str(base), str(work)]) == 1
    out = capsys.readouterr().out
    assert "true division" in out
    # exactly the new finding survives; the baselined ones stay hidden
    assert out.count("[dtype]") == 1


def test_baseline_line_churn_does_not_resurrect(tmp_path, capsys):
    import shutil

    base = tmp_path / "baseline.json"
    work = tmp_path / "work.py"
    shutil.copy(fixture("shard_bad.py"), work)
    assert cli_main(["--write-baseline", str(base), str(work)]) == 0
    # shift every finding by a few lines — the (rule, path, message) key
    # must keep matching
    work.write_text("# moved\n# moved\n# moved\n" + work.read_text())
    capsys.readouterr()
    assert cli_main(["--baseline", str(base), str(work)]) == 0


def test_json_report_carries_rule_seconds(capsys):
    import json

    assert cli_main(["--json", fixture("shape_bad.py")]) == 1
    report = json.loads(capsys.readouterr().out)
    secs = report["rule_seconds"]
    for rule in ("shape", "dtype", "shard", "lock-discipline"):
        assert rule in secs and secs[rule] >= 0, secs


def test_cli_rule_filter_shape_families(capsys):
    assert cli_main(["--rule", RULE_SHAPE, fixture("shape_bad.py")]) == 1
    out = capsys.readouterr().out
    assert RULE_SHAPE in out
    assert cli_main(["--rule", RULE_DTYPE, fixture("dtype_bad.py")]) == 1
    assert cli_main(["--rule", RULE_SHARD, fixture("shard_bad.py")]) == 1
    assert cli_main(["--rule", RULE_SHARD, fixture("shard_good.py")]) == 0
    capsys.readouterr()


def test_shape_engine_negative_slice_bounds(tmp_path):
    """Review regression: x[-k:] is k elements, not length+k — a wrong
    tail-slice model would fail correct kernels in the cross-check."""
    from kubernetes_tpu.analysis.core import SourceModule
    from kubernetes_tpu.analysis.shape import ShapeEngine, dim_str

    p = tmp_path / "slices.py"
    p.write_text(
        "import jax\nimport jax.numpy as jnp\n"
        "# ktpu: axes(x=i32[N])\n"
        "@jax.jit\n"
        "def tail(x):\n"
        "    return x[-2:], x[:-2], x[1:]\n"
    )
    eng = ShapeEngine().run([SourceModule.load(str(p))])
    a, b, c = eng.root_returns["slices.tail"].items
    assert a.shape == (2,)
    assert dim_str(b.shape[0]) == "N-2"
    assert dim_str(c.shape[0]) == "N-1"


def test_shape_engine_maps_a_lambda_over_the_leaves_of_one_tree(tmp_path):
    """``jax.tree_util.tree_map(fn, tree)`` over ONE tuple of arrays is
    ``fn`` leaf by leaf (gang.precompute gathers the batch's representative
    rows that way): a row gather by ``rows`` [U] swaps each leaf's leading
    axis and keeps the others, instead of turning the whole tree unknown."""
    from kubernetes_tpu.analysis.core import SourceModule
    from kubernetes_tpu.analysis.shape import ShapeEngine, dim_str

    p = tmp_path / "rows.py"
    p.write_text(
        "import jax\nimport jax.numpy as jnp\n"
        "# ktpu: axes(a=i32[P,N], b=bool[P], rows=i32[U])\n"
        "@jax.jit\n"
        "def take(a, b, rows):\n"
        "    return jax.tree_util.tree_map(lambda x: x[rows], (a, b))\n"
    )
    eng = ShapeEngine().run([SourceModule.load(str(p))])
    a, b = eng.root_returns["rows.take"].items
    assert [dim_str(d) for d in a.shape] == ["U", "N"] and a.dtype == "i32"
    assert [dim_str(d) for d in b.shape] == ["U"] and b.dtype == "bool"


def test_accum_contract_not_erased_by_summary_reuse(tmp_path):
    """Review regression: a helper first analyzed under a contract-free
    root must still report its float carry when reached from a root
    declaring accum(i64) — the summary memo key carries the contract."""
    from kubernetes_tpu.analysis.core import RULE_DTYPE, SourceModule
    from kubernetes_tpu.analysis.shape import ShapeEngine

    p = tmp_path / "accum_reuse.py"
    p.write_text(
        "import jax\nimport jax.numpy as jnp\n\n"
        "def helper(x):\n"
        "    acc = jnp.zeros((), jnp.float32)\n"
        "    def body(c):\n"
        "        return c + 1.0\n"
        "    return jax.lax.while_loop(lambda c: c < 10.0, body, acc)\n\n"
        "# ktpu: axes(x=i64[N])\n"
        "@jax.jit\n"
        "def a_root(x):\n"
        "    return helper(x)\n\n"
        "# ktpu: axes(x=i64[N])\n"
        "# ktpu: accum(i64)\n"
        "@jax.jit\n"
        "def b_root(x):\n"
        "    return helper(x)\n"
    )
    eng = ShapeEngine().run([SourceModule.load(str(p))])
    msgs = [m for r, _mod, _l, m in eng.raw_findings if r == RULE_DTYPE]
    assert any("accum(i64)" in m for m in msgs), msgs


def test_duplicate_basename_targets_both_analyzed(tmp_path):
    """Review regression: two analyzed files sharing a basename must BOTH
    be visited — the shadowed one used to drop out of shape analysis."""
    d1 = tmp_path / "a"
    d2 = tmp_path / "b"
    d1.mkdir()
    d2.mkdir()
    bad = "import jax\n\n@jax.jit\ndef kernel(x):\n    return x\n"
    (d1 / "util.py").write_text(bad)
    (d2 / "util.py").write_text(bad)
    findings = analyze_paths(shape=[str(d1 / "util.py"),
                                    str(d2 / "util.py")])
    missing = [f for f in findings if "axes" in f.message]
    assert {os.path.basename(os.path.dirname(f.path)) for f in missing} == \
        {"a", "b"}, findings


def test_load_source_rewrite_within_mtime_granularity(tmp_path):
    """Review regression: a rewrite inside the filesystem timestamp
    granularity must not serve the stale AST (content-keyed cache)."""
    from kubernetes_tpu.analysis.core import load_source

    p = tmp_path / "churn.py"
    p.write_text("x = 1\n")
    first = load_source(str(p))
    p.write_text("y = 2\n")  # same instant on coarse-mtime filesystems
    second = load_source(str(p))
    assert second.source == "y = 2\n"
    assert first.source == "x = 1\n"


def test_shapecheck_nested_root_requires_noinstantiate(tmp_path):
    """Review regression: a nested annotated root without noinstantiate
    must surface as a cross-check failure, not vanish from coverage."""
    from kubernetes_tpu.analysis import shapecheck
    from kubernetes_tpu.analysis.core import SourceModule

    p = tmp_path / "nested.py"
    p.write_text(
        "import jax\n\n"
        "def factory():\n"
        "    # ktpu: axes(x=i64[N])\n"
        "    @jax.jit\n"
        "    def inner(x):\n"
        "        return x\n"
        "    return inner\n"
    )
    res = shapecheck.cross_check(mods=[SourceModule.load(str(p))])
    assert "nested.factory.inner" in res, res
    assert "noinstantiate" in res["nested.factory.inner"][0]


def test_shapecheck_default_sizes_pairwise_distinct():
    from kubernetes_tpu.analysis import shapecheck

    vals = list(shapecheck.DEFAULT_SIZES.values())
    assert len(set(vals)) == len(vals)
    assert shapecheck.DEFAULT_DIM not in vals
