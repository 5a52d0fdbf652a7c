"""Resilience-layer tests (docs/resilience.md).

Covers the four pieces of mpi4jax_tpu/resilience/:

- fault-spec parser round-trips and host-side trigger semantics
  (``after=N`` counting, rank filtering, delay/die/corrupt actions);
- collective-watchdog registry (FIFO aliasing, expiry, the monitor
  thread, diagnostic format) and its in-graph arm/disarm bracket;
- retry_with_backoff (success after refusals, deadline error clarity,
  jitter envelope, giveup escape) and its ``init_distributed`` wiring;
- numeric guards, including the zero-cost-when-off HLO pin.

The pure-Python modules are loaded under a private package name
(``_load_isolated`` below) so the parser/registry/retry tests run even
where the installed JAX is below the package's hard floor and
``import mpi4jax_tpu`` refuses; the JAX-integration half skips there.

Fatal paths (die faults, numeric aborts, the hung-2-process watchdog
kill) are subprocess-isolated, mirroring tests/test_native.py's
abort test (ref test_common.py:60-88).  The whole module carries the
``faults`` marker: CI runs it as a dedicated lane with the native hooks
library built (docs/resilience.md "Testing").
"""

import importlib
import os
import pathlib
import re
import subprocess
import sys
import textwrap
import time
import types
import warnings

import pytest

pytestmark = pytest.mark.faults

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "mpi4jax_tpu"

try:
    import mpi4jax_tpu as _mpx_probe  # noqa: F401

    HAS_MPX = True
except RuntimeError:  # JAX below the package floor (utils/jax_compat.py)
    HAS_MPX = False

needs_mpx = pytest.mark.skipif(
    not HAS_MPX, reason="mpi4jax_tpu import refused (JAX below hard floor)"
)

_ISO_NAME = "_mpx_resilience_iso"


def _load_isolated():
    """Load the pure-Python resilience modules under a private package name.

    Bypasses ``mpi4jax_tpu/__init__.py`` (whose JAX-floor check refuses to
    import on old JAX) while preserving package context, so the modules'
    relative imports (``..utils.config``, ``.faultinject``) resolve inside
    the private namespace.  Also gives the tests module state isolated from
    any real ``mpi4jax_tpu`` import in the same process.
    """
    if _ISO_NAME in sys.modules:
        return sys.modules[_ISO_NAME]
    root = types.ModuleType(_ISO_NAME)
    root.__path__ = [str(PKG)]
    sys.modules[_ISO_NAME] = root
    for sub in ("utils", "resilience", "parallel"):
        m = types.ModuleType(f"{_ISO_NAME}.{sub}")
        m.__path__ = [str(PKG / sub)]
        sys.modules[f"{_ISO_NAME}.{sub}"] = m
        setattr(root, sub, m)
    for mod in (
        "utils.config",
        "resilience.faultinject",
        "resilience.retry",
        "resilience.watchdog",
        "resilience.runtime",
        "parallel.mesh",
    ):
        importlib.import_module(f"{_ISO_NAME}.{mod}")
    return root


ISO = _load_isolated()
fi = ISO.resilience.faultinject
wd = ISO.resilience.watchdog
rt = ISO.resilience.runtime
retry_mod = ISO.resilience.retry
config = ISO.utils.config


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Every test starts and ends with no overrides, no trigger counts, and
    no resilience environment variables."""
    rt.reset_overrides()
    fi.reset_fault_state()
    saved = {
        k: os.environ.pop(k, None)
        for k in (
            "MPI4JAX_TPU_WATCHDOG_TIMEOUT",
            "MPI4JAX_TPU_FAULT_SPEC",
            "MPI4JAX_TPU_CHECK_NUMERICS",
            "MPI4JAX_TPU_TOPOLOGY",
        )
    }
    yield
    rt.reset_overrides()
    fi.reset_fault_state()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


# ---------------------------------------------------------------------------
# fault-spec parser
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "spec",
    [
        "delay:rank=1:op=allreduce:after=3:secs=2",
        "die:rank=0:op=barrier:after=1",
        "hang:rank=3:op=allreduce:after=5",
        "hang",
        "preempt:rank=3:after=4:grace=2",
        "preempt:rank=3:op=allreduce:after=4",
        "preempt",
        "corrupt:nan:rank=2:op=allreduce",
        "corrupt:inf:op=bcast",
        "delay:secs=0.5",
        "die",
        "delay:rank=1:op=allreduce:after=3:secs=2;"
        "die:rank=0:op=barrier:after=1;hang:rank=3:op=allreduce;"
        "preempt:rank=2:after=1:grace=5;"
        "corrupt:nan:rank=2:op=allreduce",
    ],
)
def test_fault_spec_round_trips(spec):
    """parse -> canonical -> parse is a fixed point for every verb."""
    clauses = fi.parse_fault_spec(spec)
    canon = fi.canonical_spec(clauses)
    assert fi.parse_fault_spec(canon) == clauses
    assert fi.canonical_spec(fi.parse_fault_spec(canon)) == canon


def test_fault_spec_field_semantics():
    (c,) = fi.parse_fault_spec("delay:rank=1:op=AllReduce:after=3:secs=2")
    assert (c.verb, c.rank, c.op, c.after, c.secs) == (
        "delay", 1, "allreduce", 3, 2.0,  # op is lowercased
    )
    (c,) = fi.parse_fault_spec("corrupt")
    assert (c.verb, c.mode, c.rank, c.op) == ("corrupt", "nan", None, None)
    assert c.matches_op("barrier") and c.matches_op("allreduce")
    (c,) = fi.parse_fault_spec("corrupt:inf:op=bcast")
    assert c.mode == "inf"
    assert c.matches_op("bcast") and not c.matches_op("allreduce")
    assert fi.parse_fault_spec("") == ()
    assert fi.parse_fault_spec("  ; ;") == ()


@pytest.mark.parametrize(
    "bad",
    [
        "explode:rank=1",              # unknown verb
        "delay:when=now",              # unknown key
        "delay:nan",                   # bare mode on a non-corrupt verb
        "corrupt:frob",                # unknown bare mode
        "delay:rank=one",              # non-integer rank
        "delay:secs=fast",             # non-float secs
        "die:secs=2",                  # secs on a non-delay verb
        "hang:secs=2",                 # hang is forever; secs is delay-only
        "hang:nan",                    # bare mode on a non-corrupt verb
        "die:grace=2",                 # grace is preempt-only
        "preempt:secs=2",              # a notice does not sleep
        "preempt:grace=0",             # grace must be positive
        "preempt:nan",                 # bare mode on a non-corrupt verb
        "delay:rank=1:rank=2",         # duplicate key
        "delay:after=-1",              # negative after
        "delay:secs=-0.5",             # negative secs
        "delay::secs=1",               # empty field
    ],
)
def test_fault_spec_rejects_bad_clauses(bad):
    with pytest.raises(ValueError, match="fault spec clause"):
        fi.parse_fault_spec(bad)


# ---------------------------------------------------------------------------
# host-side trigger semantics (probe_host)
# ---------------------------------------------------------------------------


def test_corrupt_after_counts_per_rank():
    """``after=N``: the first N matching calls per rank run clean, every
    later one fires — and rank counters are independent."""
    (c,) = fi.parse_fault_spec("corrupt:nan:after=2")
    indexed = ((0, c),)
    assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 0  # call 1: clean
    assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 0  # call 2: clean
    assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 1  # call 3: fires
    assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 1  # keeps firing
    # rank 1 has its own counter, still in the clean window
    assert fi.probe_host(indexed, "MPI_Allreduce", 1) == 0
    fi.reset_fault_state()
    assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 0  # counters forgotten


def test_rank_filter_and_corrupt_bitmask():
    clauses = fi.parse_fault_spec("corrupt:nan:rank=1;corrupt:inf:rank=2")
    indexed = tuple(enumerate(clauses))
    assert fi.probe_host(indexed, "MPI_Bcast", 0) == 0      # matches neither
    assert fi.probe_host(indexed, "MPI_Bcast", 1) == 0b01   # clause bit 0
    assert fi.probe_host(indexed, "MPI_Bcast", 2) == 0b10   # clause bit 1


def test_delay_sleeps_only_after_threshold():
    (c,) = fi.parse_fault_spec("delay:rank=0:after=1:secs=0.2")
    indexed = ((0, c),)
    t0 = time.perf_counter()
    fi.probe_host(indexed, "MPI_Allreduce", 0)  # call 1: clean window
    clean = time.perf_counter() - t0
    t0 = time.perf_counter()
    fi.probe_host(indexed, "MPI_Allreduce", 0)  # call 2: sleeps
    fired = time.perf_counter() - t0
    assert clean < 0.15, clean
    assert fired >= 0.15, fired


def test_die_exits_process_with_code_13(monkeypatch):
    calls = []
    monkeypatch.setattr(fi.os, "_exit", lambda code: calls.append(code))
    (c,) = fi.parse_fault_spec("die:rank=3")
    fi.probe_host(((0, c),), "MPI_Barrier", 2)   # wrong rank: survives
    assert calls == []
    fi.probe_host(((0, c),), "MPI_Barrier", 3)
    assert calls == [13]


# ---------------------------------------------------------------------------
# host-scoped faults (PR 16 satellite: die-host / host=)
# ---------------------------------------------------------------------------


def test_die_host_shorthand_parses_to_the_canonical_long_form():
    (c,) = fi.parse_fault_spec("die-host:1@3")
    assert (c.verb, c.host, c.rank, c.after) == ("die", 1, None, 3)
    assert c.canonical() == "die:host=1:after=3"
    # round-trips through the long form
    assert fi.parse_fault_spec(c.canonical()) == (c,)
    # op# optional (fire immediately)
    (c0,) = fi.parse_fault_spec("die-host:0")
    assert (c0.host, c0.after) == (0, 0)
    # host= composes with other verbs and keys
    (cd,) = fi.parse_fault_spec("delay:host=1:op=allreduce:secs=0.5")
    assert (cd.verb, cd.host, cd.op, cd.secs) == (
        "delay", 1, "allreduce", 0.5)
    assert cd.canonical() == "delay:host=1:op=allreduce:secs=0.5"


@pytest.mark.parametrize("bad", [
    "die-host:",                 # missing host
    "die-host:one",              # non-integer host
    "die-host:1@x",              # non-integer op#
    "die-host:-1",               # negative host
    "die-host:1@2:after=3",      # extra fields on the shorthand
    "die:host=-2",               # negative host in long form
    "die:rank=1:host=2",         # rank and host are mutually exclusive
])
def test_host_fault_rejects_bad_clauses(bad):
    with pytest.raises(ValueError, match="fault spec clause"):
        fi.parse_fault_spec(bad)


def test_die_host_kills_every_rank_of_the_host(monkeypatch):
    """With MPI4JAX_TPU_TOPOLOGY=2x4, die-host:1 fires for ranks 4..7
    and no others — the host-row kill the drills script."""
    calls = []
    monkeypatch.setattr(fi.os, "_exit", lambda code: calls.append(code))
    os.environ["MPI4JAX_TPU_TOPOLOGY"] = "2x4"
    (c,) = fi.parse_fault_spec("die-host:1")
    indexed = ((0, c),)
    for r in (0, 1, 2, 3):
        fi.probe_host(indexed, "MPI_Barrier", r)
    assert calls == []
    for r in (4, 5, 6, 7):
        fi.probe_host(indexed, "MPI_Barrier", r)
    assert calls == [13, 13, 13, 13]
    # a rank past the spec's coverage matches nothing
    fi.probe_host(indexed, "MPI_Barrier", 11)
    assert len(calls) == 4


def test_die_host_after_counts_per_rank(monkeypatch):
    calls = []
    monkeypatch.setattr(fi.os, "_exit", lambda code: calls.append(code))
    os.environ["MPI4JAX_TPU_TOPOLOGY"] = "4,4"
    (c,) = fi.parse_fault_spec("die-host:0@2")
    indexed = ((0, c),)
    assert fi.probe_host(indexed, "MPI_Allreduce", 2) == 0  # clean 1
    assert fi.probe_host(indexed, "MPI_Allreduce", 2) == 0  # clean 2
    assert calls == []
    fi.probe_host(indexed, "MPI_Allreduce", 2)              # call 3 fires
    assert calls == [13]


def test_host_fault_without_topology_matches_nothing_and_warns_once():
    (c,) = fi.parse_fault_spec("corrupt:nan:host=0")
    indexed = ((0, c),)
    with pytest.warns(RuntimeWarning, match="MPI4JAX_TPU_TOPOLOGY"):
        assert fi.probe_host(indexed, "MPI_Allreduce", 0) == 0
    # warned once; later probes stay silent (and still match nothing)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert fi.probe_host(indexed, "MPI_Allreduce", 1) == 0
    # reset re-arms the warning (test isolation)
    fi.reset_fault_state()
    with pytest.warns(RuntimeWarning):
        fi.probe_host(indexed, "MPI_Allreduce", 0)


# ---------------------------------------------------------------------------
# watchdog registry + monitor
# ---------------------------------------------------------------------------


def test_watchdog_registry_fifo_and_snapshot():
    """Re-arming under one call id (a trace site inside fori_loop) must
    stack FIFO, not clobber — same aliasing story as the native hooks."""
    reg = wd._Registry(on_timeout=lambda entries, expired: None)
    reg.arm("MPI_Allreduce", "aabbccdd", 0, "('i',)", timeout=60.0)
    reg.arm("MPI_Allreduce", "aabbccdd", 0, "('i',)", timeout=60.0)
    snap = reg.snapshot()
    assert len(snap) == 2
    e = snap[0]
    assert e["opname"] == "MPI_Allreduce" and e["call_id"] == "aabbccdd"
    assert e["rank"] == 0 and e["axes"] == "('i',)"
    assert 0 <= e["elapsed"] < 60 and e["timeout"] == 60.0
    assert reg.check_expired() is None
    reg.disarm("aabbccdd", 0)
    assert len(reg.snapshot()) == 1
    reg.disarm("aabbccdd", 0)
    assert reg.empty()
    reg.disarm("aabbccdd", 0)  # spurious disarm is a no-op, not an error
    assert reg.empty()


def test_watchdog_disarm_without_entry_does_not_cancel_the_next_arm():
    """A disarm that finds nothing (its arm was drained with a revoked
    world, or by a claimed expiry) is dropped: the next collective under
    that key must still be watched."""
    reg = wd._Registry(on_timeout=lambda entries, expired: None)
    reg.arm("MPI_Allreduce", "aabbccdd", 0, "('i',)", timeout=60.0)
    assert reg.drain() == 1
    reg.disarm("aabbccdd", 0)  # the late disarm of the drained arm
    reg.arm("MPI_Allreduce", "aabbccdd", 0, "('i',)", timeout=60.0)
    assert len(reg.snapshot()) == 1


def test_watchdog_expiry_with_injected_clock():
    now = [100.0]
    reg = wd._Registry(on_timeout=lambda entries, expired: None,
                       clock=lambda: now[0])
    reg.arm("MPI_Gather", "12345678", 1, "('i',)", timeout=5.0)
    assert reg.check_expired() is None
    now[0] += 4.9
    assert reg.check_expired() is None
    now[0] += 0.2
    expired = reg.check_expired()
    assert expired is not None and expired["opname"] == "MPI_Gather"
    assert expired["elapsed"] == pytest.approx(5.1)


def test_watchdog_monitor_thread_fires():
    fired = []
    reg = wd._Registry(on_timeout=lambda entries, expired: fired.append(
        (entries, expired)))
    reg.arm("MPI_Allreduce", "deadbeef", 0, "('i',)", timeout=0.15)
    deadline = time.monotonic() + 5.0
    while not fired and time.monotonic() < deadline:
        time.sleep(0.05)
    assert fired, "monitor thread never fired on an expired collective"
    entries, expired = fired[0]
    assert expired["opname"] == "MPI_Allreduce"
    assert expired["elapsed"] > 0.15
    assert any(e["call_id"] == "deadbeef" for e in entries)


def test_watchdog_timeout_diagnostic_format(monkeypatch):
    """The default on_timeout dumps every in-flight op then dies through the
    host fatal path, naming the expired op/call/axes/timeout."""
    lines, fatal = [], []
    fake_native = types.ModuleType(f"{_ISO_NAME}.native")
    fake_native.host_line = lambda rank, text: lines.append((rank, text))
    fake_native.host_fatal = lambda rank, text: fatal.append((rank, text))
    monkeypatch.setitem(sys.modules, f"{_ISO_NAME}.native", fake_native)
    monkeypatch.setattr(ISO, "native", fake_native, raising=False)

    entries = [
        dict(opname="MPI_Allreduce", call_id="aabbccdd", rank=0,
             axes="('i',)", elapsed=6.01, timeout=5.0),
        dict(opname="MPI_Barrier", call_id="11223344", rank=0,
             axes="('i',)", elapsed=1.5, timeout=5.0),
    ]
    wd._default_on_timeout(entries, entries[0])
    assert len(lines) == 2
    assert "WATCHDOG | in-flight: MPI_Allreduce (call aabbccdd" in lines[0][1]
    assert "elapsed 6.01s" in lines[0][1]
    assert len(fatal) == 1
    assert ("collective watchdog: MPI_Allreduce exceeded 5s "
            "(call aabbccdd, axes=('i',))") in fatal[0][1]


# ---------------------------------------------------------------------------
# retry_with_backoff
# ---------------------------------------------------------------------------


class _Flaky:
    def __init__(self, refusals, exc=ConnectionError):
        self.left = refusals
        self.exc = exc
        self.calls = 0

    def __call__(self):
        self.calls += 1
        if self.left > 0:
            self.left -= 1
            raise self.exc(f"refused ({self.calls})")
        return "connected"


def test_retry_succeeds_after_refusals_with_exponential_envelope():
    sleeps = []
    now = [0.0]

    def sleep(s):
        sleeps.append(s)
        now[0] += s

    fn = _Flaky(4)
    out = retry_mod.retry_with_backoff(
        fn, what="test rendezvous", deadline=300.0, base_delay=1.0,
        max_delay=4.0, jitter=False, sleep=sleep, clock=lambda: now[0],
    )
    assert out == "connected" and fn.calls == 5
    # deterministic (jitter off) doubling, capped at max_delay
    assert sleeps == [1.0, 2.0, 4.0, 4.0]


def test_retry_jitter_draws_from_capped_envelope(monkeypatch):
    draws = []
    monkeypatch.setattr(
        retry_mod.random, "uniform",
        lambda a, b: draws.append((a, b)) or b,
    )
    now = [0.0]
    retry_mod.retry_with_backoff(
        _Flaky(3), deadline=300.0, base_delay=1.0, max_delay=4.0,
        sleep=lambda s: None, clock=lambda: now[0],
    )
    # full jitter: U(0, min(base * 2^n, max_delay))
    assert draws == [(0, 1.0), (0, 2.0), (0, 4.0)]


def test_retry_deadline_gives_clear_error():
    now = [0.0]

    def clock():
        return now[0]

    def sleep(s):
        now[0] += s

    fn = _Flaky(10**6)
    with pytest.raises(RuntimeError) as exc_info:
        retry_mod.retry_with_backoff(
            fn, what="coordinator connection (host:1234)", deadline=50.0,
            base_delay=10.0, max_delay=100.0, jitter=False,
            sleep=sleep, clock=clock,
        )
    msg = str(exc_info.value)
    assert "coordinator connection (host:1234)" in msg
    assert "attempt" in msg and "deadline 50s" in msg
    assert "ConnectionError" in msg
    assert isinstance(exc_info.value.__cause__, ConnectionError)
    # the sleep before the last attempt was clamped: failure lands at the
    # promised time, not one full backoff step past it
    assert now[0] == pytest.approx(50.0)


def test_retry_nonretryable_and_giveup_escape_immediately():
    fn = _Flaky(5, exc=ValueError)
    with pytest.raises(ValueError):
        retry_mod.retry_with_backoff(fn, sleep=lambda s: None)
    assert fn.calls == 1

    fn = _Flaky(5, exc=RuntimeError)
    with pytest.raises(RuntimeError, match="refused"):
        retry_mod.retry_with_backoff(
            fn, sleep=lambda s: None, giveup=lambda e: "refused" in str(e),
        )
    assert fn.calls == 1


def test_retry_rejects_nonpositive_deadline():
    with pytest.raises(ValueError, match="deadline"):
        retry_mod.retry_with_backoff(lambda: None, deadline=0)


def test_backoff_delay_pure_jitter_ceiling():
    """The pure envelope (PR 16 satellite): exponential growth, an
    explicit saturating cap, and overflow safety at absurd attempt
    counts."""
    assert retry_mod.backoff_delay(1) == 1.0
    assert retry_mod.backoff_delay(3) == 4.0
    assert retry_mod.backoff_delay(10) == 30.0          # capped
    assert retry_mod.backoff_delay(10_000) == 30.0      # still capped
    assert retry_mod.backoff_delay(
        2, base_delay=0.05, factor=3.0, max_delay=1.0) == pytest.approx(0.15)
    # base 0 = no backoff at all (and no inf * 0 NaN at huge attempts)
    assert retry_mod.backoff_delay(10_000, base_delay=0.0) == 0.0
    # factor 1 = constant
    assert retry_mod.backoff_delay(7, factor=1.0, base_delay=2.0) == 2.0


@pytest.mark.parametrize("kwargs", [
    {"attempt": 0},
    {"attempt": -3},
    {"base_delay": -1.0},
    {"factor": 0.5},
    {"max_delay": 0.0},
    {"max_delay": -2.0},
])
def test_backoff_delay_validates_parameters(kwargs):
    args = {"attempt": 1}
    args.update(kwargs)
    attempt = args.pop("attempt")
    with pytest.raises(ValueError):
        retry_mod.backoff_delay(attempt, **args)


def test_retry_validates_backoff_shape_before_first_sleep():
    calls = []

    def fn():
        calls.append(1)

    with pytest.raises(ValueError, match="factor"):
        retry_mod.retry_with_backoff(fn, factor=0.0, sleep=lambda s: None)
    assert calls == []  # rejected up front, fn never ran


def test_retry_jitter_sleeps_never_exceed_the_ceiling():
    """The jitter-bounds pin: with the real RNG, every sleep drawn over
    many failures stays within [0, backoff_delay(n)] — the stampede
    guarantee the elastic agreement reporters rely on."""
    sleeps = []
    now = [0.0]

    def sleep(s):
        sleeps.append(s)
        now[0] += 0.001   # virtual time: many attempts, tiny elapsed

    with pytest.raises(RuntimeError):
        retry_mod.retry_with_backoff(
            _Flaky(10**6), what="stampede", deadline=1.0,
            max_attempts=200, base_delay=0.01, max_delay=0.05,
            factor=2.0, sleep=sleep, clock=lambda: now[0],
        )
    assert len(sleeps) == 199
    for n, s in enumerate(sleeps, start=1):
        assert 0.0 <= s <= retry_mod.backoff_delay(
            n, base_delay=0.01, max_delay=0.05), (n, s)
    # the cap binds: late sleeps never exceed max_delay even though
    # 0.01 * 2^198 is astronomically larger
    assert max(sleeps) <= 0.05


def test_retry_exhaustion_reports_attempts_and_total_wait():
    """Satellite pin: both exhaustion errors carry the attempt count AND
    the total time spent sleeping between attempts."""
    now = [0.0]

    def sleep(s):
        now[0] += s

    with pytest.raises(RuntimeError) as exc_info:
        retry_mod.retry_with_backoff(
            _Flaky(10**6), what="agreement report", deadline=300.0,
            max_attempts=4, base_delay=1.0, jitter=False,
            sleep=sleep, clock=lambda: now[0],
        )
    msg = str(exc_info.value)
    # 3 sleeps of 1, 2, 4 seconds before the 4th failure
    assert "agreement report failed after 4 attempt(s)" in msg
    assert "7.0s of it waiting between attempts" in msg
    assert "max_attempts 4" in msg

    now[0] = 0.0
    with pytest.raises(RuntimeError) as exc_info:
        retry_mod.retry_with_backoff(
            _Flaky(10**6), what="agreement report", deadline=5.0,
            base_delay=2.0, jitter=False, sleep=sleep,
            clock=lambda: now[0],
        )
    msg = str(exc_info.value)
    assert "deadline 5s" in msg
    assert "waiting between attempts" in msg


# ---------------------------------------------------------------------------
# config resolution + runtime plan
# ---------------------------------------------------------------------------


def test_env_parsing():
    assert config.watchdog_timeout() is None            # unset
    os.environ["MPI4JAX_TPU_WATCHDOG_TIMEOUT"] = ""
    assert config.watchdog_timeout() is None            # empty
    os.environ["MPI4JAX_TPU_WATCHDOG_TIMEOUT"] = "0"
    assert config.watchdog_timeout() is None            # explicit off
    os.environ["MPI4JAX_TPU_WATCHDOG_TIMEOUT"] = "2.5"
    assert config.watchdog_timeout() == 2.5
    # nan would silently disable the watchdog while still instrumenting
    # every op (never-true comparisons); inf is meaningless as seconds
    for bad in ("-1", "soon", "nan", "inf"):
        os.environ["MPI4JAX_TPU_WATCHDOG_TIMEOUT"] = bad
        with pytest.raises(ValueError, match="MPI4JAX_TPU_WATCHDOG_TIMEOUT"):
            config.watchdog_timeout()

    assert config.check_numerics() is False
    os.environ["MPI4JAX_TPU_CHECK_NUMERICS"] = "1"
    assert config.check_numerics() is True

    assert config.fault_spec() == ""
    os.environ["MPI4JAX_TPU_FAULT_SPEC"] = "  die:rank=0  "
    assert config.fault_spec() == "die:rank=0"


def test_plan_default_off_and_per_op_clause_filter():
    assert rt.plan_for("allreduce") is None             # everything off
    rt.set_fault_spec("die:op=barrier;corrupt:op=allreduce")
    plan = rt.plan_for("allreduce")
    # clause bits index the FULL parsed spec, so the probe's bitmask stays
    # aligned with the trace-time corrupt rewrites
    assert [(bit, c.verb) for bit, c in plan.clauses] == [(1, "corrupt")]
    assert [(b, c.verb) for b, c in rt.plan_for("barrier").clauses] == [
        (0, "die")
    ]
    assert rt.plan_for("gather") is None                # matches no clause


def test_overrides_shadow_env_and_reset():
    os.environ["MPI4JAX_TPU_WATCHDOG_TIMEOUT"] = "120"
    assert rt.effective_watchdog_timeout() == 120.0
    rt.set_watchdog_timeout(0)                          # programmatic off
    assert rt.effective_watchdog_timeout() is None
    rt.set_watchdog_timeout(7)
    assert rt.effective_watchdog_timeout() == 7.0
    rt.reset_overrides()
    assert rt.effective_watchdog_timeout() == 120.0     # env rules again

    with pytest.raises(ValueError, match="fault spec clause"):
        rt.set_fault_spec("explode:rank=1")             # validated eagerly
    assert rt.effective_fault_clauses() == ()           # bad spec not kept

    # the programmatic path mirrors the env path's validation: a negative
    # timeout would kill a healthy job on the monitor's first scan
    for bad in (-1, float("nan")):
        with pytest.raises(ValueError, match="watchdog timeout"):
            rt.set_watchdog_timeout(bad)


def test_cache_token_reflects_every_knob():
    base = rt.cache_token()
    tokens = {base}
    rt.set_watchdog_timeout(30)
    tokens.add(rt.cache_token())
    rt.set_fault_spec("delay:rank=1")
    tokens.add(rt.cache_token())
    rt.set_check_numerics(True)
    tokens.add(rt.cache_token())
    # each knob must change the compiled-program cache key, or toggling it
    # would silently keep serving the stale program
    assert len(tokens) == 4
    rt.reset_overrides()
    assert rt.cache_token() == base


# ---------------------------------------------------------------------------
# init_distributed bootstrap retry
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_mesh_module(monkeypatch):
    mesh_mod = ISO.parallel.mesh
    monkeypatch.setattr(mesh_mod, "_distributed_initialized", False)
    yield mesh_mod


def test_init_distributed_retries_then_succeeds(fresh_mesh_module, monkeypatch):
    mesh_mod = fresh_mesh_module
    fn = _Flaky(2)
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize",
        lambda **kw: fn(),
    )
    mesh_mod.init_distributed(
        coordinator_address="localhost:1", num_processes=2, process_id=0,
        connect_base_delay=0.001, connect_max_delay=0.002,
    )
    assert fn.calls == 3
    assert mesh_mod._distributed_initialized
    mesh_mod.init_distributed()                 # idempotent: no reconnect
    assert fn.calls == 3


def test_init_distributed_deadline_error_names_coordinator(
        fresh_mesh_module, monkeypatch):
    mesh_mod = fresh_mesh_module
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize",
        lambda **kw: (_ for _ in ()).throw(ConnectionError("refused")),
    )
    with pytest.raises(RuntimeError) as exc_info:
        mesh_mod.init_distributed(
            coordinator_address="badhost:9999", num_processes=2, process_id=0,
            connect_deadline=0.05, connect_base_delay=0.005,
            connect_max_delay=0.01,
        )
    msg = str(exc_info.value)
    assert "badhost:9999" in msg and "attempt" in msg
    assert not mesh_mod._distributed_initialized


def test_init_distributed_already_initialized_not_retried(
        fresh_mesh_module, monkeypatch):
    mesh_mod = fresh_mesh_module
    calls = []

    def fake_init(**kw):
        calls.append(1)
        # JAX's actual double-init message (jax/_src/distributed.py)
        raise RuntimeError("distributed.initialize should only be called once.")

    monkeypatch.setattr(mesh_mod.jax.distributed, "initialize", fake_init)
    with pytest.raises(RuntimeError, match="only be called once") as exc_info:
        mesh_mod.init_distributed(
            coordinator_address="localhost:1", num_processes=2, process_id=0,
            connect_deadline=30.0,
        )
    # the giveup escape: re-raised verbatim on the first attempt, not
    # wrapped in the deadline error after 30s of futile retries
    assert len(calls) == 1
    assert "failed after" not in str(exc_info.value)


# ===========================================================================
# JAX-integration half (needs a working mpi4jax_tpu import)
# ===========================================================================


def _subprocess_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


_SUBPROCESS_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import mpi4jax_tpu as mpx
""")


@needs_mpx
def test_hlo_byte_identical_when_disabled(monkeypatch):
    """Acceptance pin: with every resilience feature off (the default) the
    lowered HLO is byte-identical to an uninstrumented build, and turning a
    knob on changes it (so the pin cannot pass vacuously)."""
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu.resilience import runtime as real_rt

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    x = jnp.ones((8, 4))
    default_off = jax.jit(f).lower(x).as_text()
    with monkeypatch.context() as m:
        # the uninstrumented build: the dispatch layer never consults a plan
        m.setattr(real_rt, "plan_for", lambda opname: None)
        uninstrumented = jax.jit(f).lower(x).as_text()
    assert default_off == uninstrumented

    real_rt.set_check_numerics(True)
    try:
        guarded = jax.jit(f).lower(x).as_text()
    finally:
        real_rt.reset_overrides()
    assert guarded != default_off


@needs_mpx
def test_delay_fault_injects_at_dispatch():
    """A delay clause observably slows only the post-``after`` calls of the
    matching op, through the real dispatch path."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import resilience

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    x = jnp.arange(8.0)[:, None]
    resilience.set_fault_spec("delay:rank=1:op=allreduce:after=2:secs=0.4")
    resilience.reset_fault_state()
    try:
        np.asarray(f(x))                   # call 1: clean window + compile
        t0 = time.perf_counter()
        clean_run = np.asarray(f(x))       # call 2: clean window, cached
        clean = time.perf_counter() - t0
        t0 = time.perf_counter()
        fired_run = np.asarray(f(x))       # call 3: rank 1 sleeps 0.4s
        fired = time.perf_counter() - t0
    finally:
        resilience.reset_overrides()
        resilience.reset_fault_state()
    # values unharmed: delay is a straggler, not corruption
    assert (clean_run == 28).all() and (fired_run == 28).all()
    assert fired >= clean + 0.25, (clean, fired)


@needs_mpx
def test_watchdog_brackets_collective_cleanly():
    """With a generous timeout the watchdog arms and disarms around a healthy
    collective: values are untouched and nothing stays in flight."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import native, resilience
    from mpi4jax_tpu.resilience import watchdog as real_wd

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    # force the Python-fallback registry even where the native hooks
    # library is built, so this test pins the io_callback bracket (the
    # native bracket's kill path is exercised by the subprocess tests)
    import unittest.mock

    resilience.set_watchdog_timeout(60)
    try:
        with unittest.mock.patch.object(
            native, "watchdog_supported", lambda: False
        ):
            out = np.asarray(f(jnp.arange(8.0)[:, None]))
    finally:
        resilience.reset_overrides()
    assert (out == 28).all()
    deadline = time.monotonic() + 5.0
    while not real_wd.registry_empty() and time.monotonic() < deadline:
        time.sleep(0.05)  # disarm callbacks may trail block_until_ready
    assert real_wd.registry_empty(), real_wd.inflight_snapshot()


@needs_mpx
def test_watchdog_disarm_never_runs_before_its_arm(monkeypatch):
    """Arm, collective and disarm are ordered by real data dependence.
    XLA:CPU expands ``optimization_barrier`` ties away before it schedules;
    with only those, every disarm inside a ``fori_loop`` body ran ahead of
    its arm, and the arm left in flight killed the process at its timeout."""
    import jax
    import jax.numpy as jnp

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import native, resilience
    from mpi4jax_tpu.resilience import watchdog as real_wd

    events = []
    reg = real_wd._registry
    real_arm, real_disarm = reg.arm, reg.disarm

    def arm(opname, call_id, rank, axes, timeout):
        events.append((1, call_id, rank))
        real_arm(opname, call_id, rank, axes, timeout)

    def disarm(call_id, rank):
        events.append((-1, call_id, rank))
        real_disarm(call_id, rank)

    monkeypatch.setattr(reg, "arm", arm)
    monkeypatch.setattr(reg, "disarm", disarm)
    monkeypatch.setattr(native, "watchdog_supported", lambda: False)

    def step(v):
        s, _ = mpx.allreduce(v, op=mpx.SUM)
        return mpx.varying(s * 0.25 + v * 0.5)

    unroll = 4
    resilience.set_watchdog_timeout(60)
    try:
        mesh = mpx.make_world_mesh()
        comm = mpx.Comm(mesh.axis_names[0], mesh=mesh)
        k = comm.Get_size()
        x = jnp.ones((k, 4), jnp.float32)
        jax.block_until_ready(
            mpx.compile(step, x, comm=comm, unroll=unroll)(x))
        # array-less: ordered through its token
        jax.block_until_ready(mpx.barrier(comm=comm).value)
    finally:
        resilience.reset_overrides()
    deadline = time.monotonic() + 5.0
    while not real_wd.registry_empty() and time.monotonic() < deadline:
        time.sleep(0.05)  # disarm callbacks may trail block_until_ready
    assert real_wd.registry_empty(), real_wd.inflight_snapshot()
    # megastep bracket + one in-loop allreduce per step + the barrier
    assert len(events) == 2 * k * (1 + unroll + 1), len(events)
    in_flight = {}
    for delta, call_id, rank in events:
        depth = in_flight.get((call_id, rank), 0) + delta
        assert depth >= 0, f"disarm before arm: call {call_id} rank {rank}"
        in_flight[(call_id, rank)] = depth


@needs_mpx
def test_die_fault_kills_process_from_env_spec():
    """End-to-end ``die``: the spec comes in through the environment, fires
    at the dispatch point, and kills the process with exit code 13."""
    script = _SUBPROCESS_PRELUDE + textwrap.dedent("""
        import numpy as np

        @mpx.spmd
        def f(x):
            res, _ = mpx.allreduce(x, op=mpx.SUM)
            return res

        out = np.asarray(f(jnp.arange(8.0)[:, None]))
        assert (out == 28).all()           # call 1 is inside the after window
        f(jnp.arange(8.0)[:, None]).block_until_ready()
        print("SHOULD NOT REACH", flush=True)
    """)
    env = _subprocess_env()
    env["MPI4JAX_TPU_FAULT_SPEC"] = "die:rank=5:op=allreduce:after=1"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert proc.returncode == 13, proc.stderr[-4000:]
    assert "r5 | FAULT | die injected in MPI_Allreduce" in proc.stderr
    assert "SHOULD NOT REACH" not in proc.stdout


@needs_mpx
def test_corrupt_nan_aborts_under_check_numerics():
    """corrupt:nan + CHECK_NUMERICS: the injected NaN is caught at the
    collective boundary and the abort names the op."""
    script = _SUBPROCESS_PRELUDE + textwrap.dedent("""
        from mpi4jax_tpu import native
        if not native.available():
            native.build(verbose=False)

        @mpx.spmd
        def f(x):
            res, _ = mpx.allreduce(x, op=mpx.SUM)
            return res

        f(jnp.arange(8.0)[:, None]).block_until_ready()
        print("SHOULD NOT REACH", flush=True)
    """)
    env = _subprocess_env()
    env["MPI4JAX_TPU_FAULT_SPEC"] = "corrupt:nan:rank=2:op=allreduce"
    env["MPI4JAX_TPU_CHECK_NUMERICS"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert proc.returncode != 0, proc.stdout
    assert "FAULT | corrupt:nan injected in MPI_Allreduce" in proc.stderr
    assert re.search(
        r"FATAL: MPI_Allreduce: non-finite (input|output) detected "
        r"\(MPI4JAX_TPU_CHECK_NUMERICS", proc.stderr,
    ), proc.stderr[-4000:]
    assert "SHOULD NOT REACH" not in proc.stdout


@needs_mpx
def test_check_numerics_passes_finite_values():
    """The guard is not trigger-happy: finite traffic flows untouched."""
    import jax.numpy as jnp
    import numpy as np

    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import resilience

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    resilience.set_check_numerics(True)
    try:
        out = np.asarray(f(jnp.arange(8.0)[:, None]))
    finally:
        resilience.reset_overrides()
    assert (out == 28).all()


# the flagship fail-fast drill (ISSUE acceptance): a 2-process job where an
# injected `die` kills rank 1; rank 0 hangs in the next collective and its
# watchdog must abort it — naming the in-flight op — within 2x the timeout.
WATCHDOG_TIMEOUT_S = 5.0

_HANG_WORKER = textwrap.dedent("""
    import os, sys
    proc_id = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, sys.argv[3])
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import mpi4jax_tpu as mpx
    from mpi4jax_tpu import resilience

    mpx.init_distributed(
        coordinator_address=f"localhost:{port}",
        num_processes=2, process_id=proc_id,
    )
    assert jax.device_count() == 2

    # rank 1 dies in its second allreduce; every rank's watchdog is armed
    resilience.set_watchdog_timeout(float(sys.argv[4]))
    resilience.set_fault_spec("die:rank=1:op=allreduce:after=1")

    @mpx.spmd
    def f(x):
        res, _ = mpx.allreduce(x, op=mpx.SUM)
        return res

    x = jnp.arange(2.0)
    out = f(x)                      # step 1: clean for both ranks
    for s in out.addressable_shards:
        assert np.asarray(s.data)[0] == 1.0
    print(f"STEP1_OK {proc_id}", flush=True)
    try:
        f(x).block_until_ready()    # step 2: rank 1 dies; rank 0 hangs
        print(f"SHOULD NOT REACH {proc_id}", flush=True)
    except Exception as e:
        # the peer's death surfaced as a collective error instead of a
        # hang; the watchdog entry armed for this collective was never
        # disarmed, so the monitor still owes the diagnostic + kill --
        # wait for it rather than exiting on our own terms
        import time
        print(f"COLLECTIVE_ERROR {proc_id}: {e}", flush=True)
        time.sleep(120)
""")


@pytest.mark.slow
@needs_mpx
def test_watchdog_aborts_hung_rank_after_injected_death():
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _HANG_WORKER, str(i), port, str(REPO),
             str(WATCHDOG_TIMEOUT_S)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    # generous wall budget for startup + step 1; the 2x-timeout bound is
    # asserted from the watchdog's own elapsed measurement below, which
    # starts when the doomed collective arms
    try:
        out1, err1 = procs[1].communicate(timeout=300)
        out0, err0 = procs[0].communicate(timeout=300)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    assert procs[1].returncode == 13, (out1, err1[-4000:])
    assert "die injected in MPI_Allreduce" in err1
    assert "STEP1_OK 1" in out1

    # rank 0: loud watchdog death, not a hang — diagnostics name the op
    assert procs[0].returncode != 0, (out0, err0[-4000:])
    assert "SHOULD NOT REACH 0" not in out0
    assert "STEP1_OK 0" in out0
    m = re.search(
        r"WATCHDOG \| in-flight: MPI_Allreduce \(call [0-9a-f]{8}, "
        r"axes=.*elapsed (\d+\.\d+)s\)", err0)
    assert m, err0[-4000:]
    elapsed = float(m.group(1))
    assert elapsed <= 2 * WATCHDOG_TIMEOUT_S, elapsed
    assert re.search(
        r"FATAL: collective watchdog: MPI_Allreduce exceeded "
        + re.escape(f"{WATCHDOG_TIMEOUT_S:g}") + "s", err0), err0[-4000:]
