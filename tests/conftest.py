"""Test configuration: the CPU backend with 8 virtual devices.

Sharding tests run against XLA:CPU with
``--xla_force_host_platform_device_count=8`` (see the driver's
``dryrun_multichip`` contract). Both settings go into the environment
before jax is imported, so the python subprocesses that tests spawn
(shell CLI, cluster choreography) inherit them and stay off any
accelerator too.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# ---------------------------------------------------------------------------
# Runtime lock-order checking (the dynamic half of seaweedlint).
#
# Record mode for the whole tier-1 suite: every threading.Lock/RLock
# created by seaweedfs_tpu code is wrapped, acquisition order is
# recorded per creation site, and an observed A→B / B→A inversion
# fails the session at the end (see pytest_sessionfinish below).
# Opt out with SEAWEED_LOCKCHECK=0; use =raise to fault at the
# offending acquire instead of at session end.
# ---------------------------------------------------------------------------

os.environ.setdefault("SEAWEED_LOCKCHECK", "1")

from seaweedfs_tpu.util import lockcheck  # noqa: E402

lockcheck.install_from_env()

# ---------------------------------------------------------------------------
# Runtime pooled-buffer checking (the dynamic half of SW5xx).
#
# Armed for the whole tier-1 suite: HostBufferPool slabs are
# generation-tagged and poisoned on recycle, and the writeback workers
# verify every positioned write's source generation before and after
# the pwritev — a pooled view consumed after its recycle (the PR 12
# ascontiguousarray race class) fails deterministically as a
# WriterError instead of as rare shard corruption. Opt out with
# SEAWEED_BUFCHECK=0; use =protect to also PROT_NONE free slabs.
# ---------------------------------------------------------------------------

os.environ.setdefault("SEAWEED_BUFCHECK", "1")

from seaweedfs_tpu.util import bufcheck  # noqa: E402

bufcheck.install_from_env()

# ---------------------------------------------------------------------------
# Eraser lockset race checking (the dynamic half of SW801).
#
# Armed for the whole tier-1 suite: registered shared objects
# (pipeline pools, stage stats, metrics registries, cache tiers, the
# ingress server) intercept attribute writes and track the candidate
# lockset per (object, attribute); a write whose lockset intersection
# goes empty across threads is a race report, and any report left at
# session end fails the run. Opt out with SEAWEED_RACECHECK=0; use
# =raise to fault at the offending write.
# ---------------------------------------------------------------------------

os.environ.setdefault("SEAWEED_RACECHECK", "1")

from seaweedfs_tpu.util import racecheck  # noqa: E402

racecheck.install_from_env()


import pytest  # noqa: E402


def _interpret_kernels(monkeypatch):
    from seaweedfs_tpu.ops import rs_jax, rs_pallas
    for name in ("apply_gf_matrix_words", "apply_gf_matrix_words_mat"):
        real = getattr(rs_pallas, name)
        monkeypatch.setattr(
            rs_pallas, name,
            lambda *a, _real=real, **kw: _real(*a, **{**kw,
                                                      "interpret": True}))
    caches = (rs_jax._jitted_apply, rs_jax._jitted_apply_multi,
              rs_jax._jitted_apply_mat)
    for cache in caches:
        cache.cache_clear()
    yield
    for cache in caches:
        cache.cache_clear()


@pytest.fixture()
def interpreted_kernels(monkeypatch):
    """Both word-form kernel entries (``rs_words``, and ``rs_words_mat``
    of the reconstruct paths) run by the Pallas interpreter, and the
    jitted steps built around them dropped before and after, so that no
    test meets a step another one traced."""
    yield from _interpret_kernels(monkeypatch)


@pytest.fixture(scope="module")
def interpreted_kernels_module():
    """The same for a whole test file: its cases share the steps they
    trace (a file that counts traces, or runs one program on many
    matrices, compiles each once)."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        yield from _interpret_kernels(monkeypatch)


def pytest_configure(config):
    # Tier-1 runs with -m 'not slow'; the slow tier holds the
    # full-scale simulation acceptance run (minutes of wall time).
    config.addinivalue_line(
        "markers", "slow: full-scale runs excluded from tier-1 "
                   "(select with -m slow)")


# ---------------------------------------------------------------------------
# Durability policy for tests.
#
# The production default is fsync-on-commit, but paying two fsyncs per
# appended needle turns write-heavy race tests into multi-minute runs
# on slow disks (tests/test_vacuum_races.py spins writer threads for
# five whole compact cycles). Tests exercise the append/compact logic,
# not the disk's flush latency, so run the suite in "off" mode — the
# pre-durability-policy behavior. Crash-consistency tests that DO need
# the fsync semantics opt back in per-test (tests/test_crashfs.py's
# autouse fixture runs after this one and wins).
# ---------------------------------------------------------------------------

import pytest  # noqa: E402

from seaweedfs_tpu.util import durability  # noqa: E402


@pytest.fixture(autouse=True)
def _fast_test_durability():
    durability.configure(mode="off")
    yield
    durability.configure(mode="off")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    viols = lockcheck.violations()
    if viols:
        terminalreporter.section(
            "seaweed lockcheck: lock-order violations")
        for v in viols:
            terminalreporter.write_line(v.describe())
    bviols = bufcheck.violations()
    if bviols:
        terminalreporter.section(
            "seaweed bufcheck: dangling pooled-buffer views")
        for v in bviols:
            terminalreporter.write_line(v)
    rviols = racecheck.races()
    if rviols:
        terminalreporter.section(
            "seaweed racecheck: unsynchronized shared-state writes")
        for v in rviols:
            terminalreporter.write_line(v.describe())


def pytest_sessionfinish(session, exitstatus):
    # Tests that deliberately provoke inversions (tests/test_lockcheck.py)
    # or races (tests/test_racecheck.py) clean up after themselves via
    # lockcheck.reset() / racecheck.reset(); anything left here is a
    # real bug observed somewhere in the suite.
    if lockcheck.violations() and session.exitstatus == 0:
        session.exitstatus = 1
    if racecheck.races() and session.exitstatus == 0:
        session.exitstatus = 1

# ---------------------------------------------------------------------------
# Prometheus exposition-format mini parser (shared by metrics tests).
# ---------------------------------------------------------------------------

import re  # noqa: E402

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?"
    r" (?P<value>[^ ]+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape_label(v: str) -> str:
    return (v.replace("\\\\", "\x00").replace('\\"', '"')
            .replace("\\n", "\n").replace("\x00", "\\"))


def parse_exposition(text: str) -> dict:
    """Parse exposition text -> {name: [(labels_dict, float_value)]};
    raises ValueError on any malformed line (that IS the test)."""
    samples: dict = {}
    types: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"malformed sample line: {line!r}")
        labels = {}
        raw = m.group("labels")
        if raw:
            consumed = 0
            for lm in _LABEL_RE.finditer(raw):
                labels[lm.group(1)] = _unescape_label(lm.group(2))
                consumed = lm.end()
            # everything between matches must be commas only
            leftovers = _LABEL_RE.sub("", raw).replace(",", "").strip()
            if leftovers or consumed != len(raw):
                raise ValueError(f"malformed labels: {raw!r}")
        v = m.group("value")
        value = float("inf") if v == "+Inf" else float(v)
        samples.setdefault(m.group("name"), []).append((labels, value))
    parse_exposition.last_types = types
    return samples
