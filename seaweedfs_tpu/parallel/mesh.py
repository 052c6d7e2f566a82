"""Sharded EC steps over a jax.sharding.Mesh.

The reference distributes EC work by placing the 14 shard files on
different servers and moving bytes with gRPC (SURVEY.md §2 "parallelism
strategies" table). The TPU-native equivalent keeps the math on a device
mesh instead:

* ``dp`` (volume/batch axis): independent volumes spread across chips —
  the analog of many volume servers encoding concurrently.
* ``sp`` (stripe axis): one volume's byte range split across chips — the
  analog of the reference striping one .dat over shard servers. The
  bitsliced codec is positionwise over 128-byte groups, so stripe-axis
  sharding needs NO communication for encode; only the global integrity
  checksum crosses chips (one psum over the mesh, riding ICI).

Steps are built with shard_map so the collective structure is explicit
and compiles to XLA collectives; the same code runs on a virtual CPU mesh
(tests, the driver's dry-run) and a real TPU pod slice.

Production routing (docs/mesh.md): the pipeline's encode/rebuild/batch
paths call :func:`routing_mesh` — an explicit ``[mesh]`` TOML section or
``-mesh dp,sp`` shell flag pins a mesh (virtual CPU meshes included, the
CI recipe), a multi-chip accelerator auto-shards adaptively, and
everything else stays on the single-device host fast path. The compute
stage splits into prepare (H2D shard placement — :func:`prepare_batch`)
and apply (the mesh step — :func:`apply_prepared`) so ``[pipeline]
double_buffer`` can overlap the next batch's transfer with the current
batch's collective.
"""

from __future__ import annotations

import collections
import contextlib
import math
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import bitslice, rs_jax
from ..ops.rs_jax import Encoder

GROUP = bitslice.GROUP_BYTES


def _auto_factor(n: int) -> tuple[int, int]:
    """Most-square (dp, sp) with sp >= dp (stripe parallelism is
    communication-free here, so over-sharding it is harmless)."""
    dp = 1
    for f in range(int(math.isqrt(n)), 0, -1):
        if n % f == 0:
            dp = f
            break
    return dp, n // dp


def make_mesh(devices=None, dp: Optional[int] = None,
              sp: Optional[int] = None) -> Mesh:
    """Build a (dp, sp) mesh over the given devices (default: all).

    Without explicit sizes, picks the most-square factorization with the
    stripe axis at least as large as the batch axis (stripe parallelism
    is communication-free here, so over-sharding it is harmless).

    An explicit request is honored or refused, never silently
    re-factored: any (dp, sp) that cannot tile the device count raises
    with the factorization that would.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if dp is not None and dp < 1 or sp is not None and sp < 1:
        raise ValueError(f"mesh axes must be positive, got dp={dp} sp={sp}")
    if dp is None and sp is None:
        dp, sp = _auto_factor(n)
    elif dp is None:
        if n % sp:
            raise ValueError(
                f"sp={sp} does not divide device count {n} "
                f"(auto factorization would be dp,sp = "
                f"{_auto_factor(n)[0]},{_auto_factor(n)[1]})")
        dp = n // sp
    elif sp is None:
        if n % dp:
            raise ValueError(
                f"dp={dp} does not divide device count {n} "
                f"(auto factorization would be dp,sp = "
                f"{_auto_factor(n)[0]},{_auto_factor(n)[1]})")
        sp = n // dp
    if dp * sp != n:
        raise ValueError(
            f"dp*sp = {dp}*{sp} = {dp * sp} != device count {n}: an "
            f"explicit mesh must tile ALL local devices (want dp*sp == "
            f"{n}, e.g. {_auto_factor(n)[0]},{_auto_factor(n)[1]})")
    dev_array = np.array(devices).reshape(dp, sp)
    return Mesh(dev_array, axis_names=("dp", "sp"))


# --------------------------------------------------------------------------
# configuration — the [mesh] TOML section / the -mesh shell flag
# --------------------------------------------------------------------------

class MeshConfigError(ValueError):
    """A [mesh]/-mesh request that cannot tile the local devices."""


@dataclass
class MeshConfig:
    """The ``[mesh]`` TOML section (docs/mesh.md): pin an EXPLICIT
    device mesh for the production encode/rebuild paths. Disabled (the
    default) keeps the auto routing — multi-chip accelerators shard
    adaptively, everything else takes the single-device host fast
    path. ``0`` for an axis means "derive" (most-square
    factorization). Flags > TOML > defaults, like every other
    subsystem (util/config.py)."""

    enabled: bool = False
    dp: int = 0
    sp: int = 0


_CONFIG = MeshConfig()


def current() -> MeshConfig:
    return _CONFIG


def configure(**kw) -> None:
    """Set config fields; None values keep their current setting."""
    for key, val in kw.items():
        if not hasattr(_CONFIG, key):
            raise TypeError(f"unknown mesh config key {key!r}")
        if val is not None:
            cur = getattr(_CONFIG, key)
            setattr(_CONFIG, key, type(cur)(val))


def configure_from(conf: dict) -> None:
    """Apply a loaded TOML dict's ``[mesh]`` block (missing keys keep
    their current values)."""
    from ..util import config as config_mod
    sect = config_mod.lookup(conf, "mesh")
    if not isinstance(sect, dict):
        return
    configure(**{k: sect.get(k) for k in ("enabled", "dp", "sp")})


def parse_spec(spec: str) -> tuple[int, int]:
    """``-mesh dp,sp`` -> (dp, sp); ``-mesh auto`` -> (0, 0), the
    most-square factorization of the local device count."""
    text = (spec or "").strip().lower()
    if text in ("auto", ""):
        return 0, 0
    parts = text.split(",")
    try:
        if len(parts) != 2:
            raise ValueError
        dp, sp = int(parts[0]), int(parts[1])
        if dp < 1 or sp < 1:
            raise ValueError
    except ValueError:
        raise MeshConfigError(
            f"bad mesh spec {spec!r}: want 'dp,sp' with positive "
            f"integers (e.g. '2,4') or 'auto'") from None
    return dp, sp


@contextlib.contextmanager
def scoped(spec: str):
    """Enable an explicit mesh for one command/job (the ``-mesh`` shell
    flag; the ec_encode job param): parse, validate against the local
    device count — a clear :class:`MeshConfigError` BEFORE any work
    starts — and restore the previous config on exit. Yields the Mesh."""
    dp, sp = parse_spec(spec)
    prev = (_CONFIG.enabled, _CONFIG.dp, _CONFIG.sp)
    _CONFIG.enabled, _CONFIG.dp, _CONFIG.sp = True, dp, sp
    try:
        yield configured_mesh()
    finally:
        _CONFIG.enabled, _CONFIG.dp, _CONFIG.sp = prev


_configured_cache: dict = {}   # (n_devices, dp, sp) -> Mesh


def configured_mesh() -> Optional[Mesh]:
    """The ``[mesh]``-configured Mesh over all local devices, or None
    when the section is disabled. An explicit (dp, sp) that cannot tile
    the device count is a :class:`MeshConfigError` — the request is
    honored or refused, never silently re-factored."""
    if not _CONFIG.enabled:
        return None
    n = len(jax.devices())
    key = (n, _CONFIG.dp, _CONFIG.sp)
    mesh = _configured_cache.get(key)
    if mesh is None:
        try:
            mesh = make_mesh(dp=_CONFIG.dp or None,
                             sp=_CONFIG.sp or None)
        except ValueError as e:
            auto = _auto_factor(n)
            raise MeshConfigError(
                f"mesh dp={_CONFIG.dp or 'auto'},"
                f"sp={_CONFIG.sp or 'auto'} cannot tile the {n} local "
                f"device(s): {e}. Pass -mesh dp,sp with dp*sp == {n} "
                f"(e.g. '{auto[0]},{auto[1]}'), or -mesh auto.") from e
        _configured_cache.clear()  # one live shape; drop stale counts
        _configured_cache[key] = mesh
    return mesh


#: Sentinel :func:`routing_mesh` returns for "shard, but let the auto
#: path adapt the mesh per batch" (multi-chip accelerators).
AUTO = object()


def routing_mesh():
    """What the production twin paths (pipeline encode / rebuild /
    coalescing batcher) should do: a Mesh when ``[mesh]`` is enabled
    (virtual CPU meshes included — the CI recipe), the :data:`AUTO`
    sentinel on a multi-chip accelerator (adaptive dp, Pallas
    kernels), or None for the single-device host fast path."""
    mesh = configured_mesh()
    if mesh is not None:
        return mesh
    from ..ops.rs_jax import _use_pallas
    if _use_pallas() and len(jax.devices()) > 1:
        return AUTO
    return None


def make_sharded_encode_step(encoder: Encoder, mesh: Mesh):
    """jitted (B, k, S) u8 -> ((B, m, S) parity, scalar checksum).

    Input sharded (dp, -, sp); parity keeps the same sharding; the
    checksum is the byte-sum of the parity **mod 2^32** (uint32
    accumulation), psum-reduced over BOTH axes so every chip holds the
    global value (the cross-chip integrity handshake a multi-server
    encode does over gRPC in the reference). Host-side verifiers must
    reduce mod 2^32 too.
    """
    coefs = encoder.parity_coefs

    def step(x):
        parity = bitslice.apply_gf_matrix(coefs, x)
        local = jnp.sum(parity.astype(jnp.uint32), dtype=jnp.uint32)
        total = jax.lax.psum(local, ("dp", "sp"))
        return parity, total

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=P("dp", None, "sp"),
        out_specs=(P("dp", None, "sp"), P()),
    )
    return jax.jit(mapped)


def make_sharded_train_step(encoder: Encoder, mesh: Mesh,
                            lost: tuple[int, ...] = (0,)):
    """The FULL EC 'training step' used by the driver's multi-chip dry run:
    encode -> drop ``lost`` shards -> reconstruct them -> verify they match
    the originals, returning ((B, m, S) parity, scalar mismatch count).

    Exercises the complete device-side math (both matrix applications) plus
    a global psum, all under one jit over the mesh.
    """
    k, m = encoder.data_shards, encoder.parity_shards
    total_n = encoder.total_shards
    parity_coefs = encoder.parity_coefs
    lost = tuple(sorted(lost))
    present = [i for i in range(total_n) if i not in lost]
    rebuild_coefs = encoder.decode_matrix_rows(present, list(lost))

    def step(x):
        parity = bitslice.apply_gf_matrix(parity_coefs, x)
        full = jnp.concatenate([x, parity], axis=1)
        originals = full[:, lost, :]
        survivors = full[:, present[:k], :]
        rebuilt = bitslice.apply_gf_matrix(rebuild_coefs, survivors)
        local_bad = jnp.sum((rebuilt != originals).astype(jnp.uint32),
                            dtype=jnp.uint32)
        mismatches = jax.lax.psum(local_bad, ("dp", "sp"))
        return parity, mismatches

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=P("dp", None, "sp"),
        out_specs=(P("dp", None, "sp"), P()),
    )
    return jax.jit(mapped)


def make_sharded_rebuild_step(encoder: Encoder, mesh: Mesh,
                              present, wanted):
    """jitted survivors (B, k, S) u8 -> ((B, len(wanted), S) rebuilt,
    scalar u32 byte-sum checksum psum-reduced over the mesh).

    The sp axis shards the BYTE RANGE of real shard files: the decode
    matrix application is positionwise over 128-byte groups, so each
    chip rebuilds its slice of the lost shards from its slice of the
    survivors with no communication — the cross-chip part is only the
    integrity psum. ``present`` may be ANY survivor set (uneven mixes
    of data and parity ids; the first k are used), matching how
    ec.rebuild reads whichever shards are still alive (SURVEY §3.3)."""
    rows = encoder.decode_matrix_rows(list(present), list(wanted))

    def step(surv):
        rebuilt = bitslice.apply_gf_matrix(rows, surv)
        local = jnp.sum(rebuilt.astype(jnp.uint32), dtype=jnp.uint32)
        return rebuilt, jax.lax.psum(local, ("dp", "sp"))

    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=P("dp", None, "sp"),
        out_specs=(P("dp", None, "sp"), P()),
    )
    return jax.jit(mapped)


_auto_meshes: dict = {}       # dp-choice -> Mesh over all devices
_auto_n_devices = 0
#: (mesh shape, coefs shape, coefs bytes) -> jitted step; LRU-bounded —
#: rebuilds mint one decode matrix per loss pattern, and a long-lived
#: repair daemon must not accumulate an executable per pattern forever.
_auto_steps: "collections.OrderedDict" = collections.OrderedDict()
_AUTO_STEPS_CAP = 32


def _make_apply_only_step(coefs: np.ndarray, mesh: Mesh):
    """Checksum-free coefficient-rows application for the production
    paths (encode: parity rows; rebuild: decode rows): the integrity
    psum belongs to the verify-style steps, not to every data batch —
    paying a full reduction plus a both-axes collective per batch would
    be wasted ICI traffic. On an accelerator the per-shard math is the
    fused Pallas kernel; elsewhere the XLA network.

    The input shards are donated under rs_jax.donation_enabled()'s rule
    (a TPU backend only): every caller
    feeds a freshly device_put array that is never reused, so XLA may
    release the input HBM inside the computation — the same early-free
    win the single-device word-form path gets from _jitted_apply."""
    from ..ops import rs_pallas
    if _real_accelerator():
        def step(x):
            return rs_pallas.apply_gf_matrix(coefs, x)
    else:
        def step(x):
            return bitslice.apply_gf_matrix(coefs, x)
    # check_vma=False: the Pallas call's out_shape carries no
    # varying-manual-axes annotation, and shard_map's default check
    # refuses to trace it. The step is position-wise — no collective,
    # every output block varies over both axes exactly as its input
    # block does — so there is nothing for the check to find.
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=P("dp", None, "sp"),
        out_specs=P("dp", None, "sp"),
        check_vma=False,
    )
    donate = (0,) if rs_jax.donation_enabled() else ()

    def rs_mesh_step(x):
        return mapped(x)
    # the trace names the step by its mesh, not "mapped"
    rs_mesh_step.__name__ = rs_mesh_step.__qualname__ = \
        f"rs_mesh_dp{mesh.shape['dp']}_sp{mesh.shape['sp']}"
    return jax.jit(rs_mesh_step, donate_argnums=donate)


def _real_accelerator() -> bool:
    """The REAL backend decides kernel + granule (Mosaic only lowers on
    TPU) — deliberately decoupled from rs_jax._use_pallas, which the
    routing gates (and their tests) may override."""
    return jax.default_backend() == "tpu"


def _granule(sp: int) -> int:
    """Per-shard S granule for the auto-sharded encode: the Pallas
    kernel needs SEG_BYTES per device shard; the XLA network only the
    packing group. Follows the REAL backend, like the step kernel."""
    from ..ops import rs_pallas
    return sp * (rs_pallas.SEG_BYTES if _real_accelerator() else GROUP)


# --------------------------------------------------------------------------
# telemetry — pipe.compute split into dispatch (H2D shard placement)
# vs collective (the mesh step) time, plus per-axis gauges
# --------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_TOTALS = {"batches": 0, "bytes_in": 0, "bytes_out": 0,
           "dispatch_seconds": 0.0, "collective_seconds": 0.0}
_LAST_SHAPE = {"dp": 0, "sp": 0}
#: device id -> input bytes placed on it: shows that a mesh spreads
#: over every local device rather than piling onto the first
_DEVICE_BYTES: dict = {}
#: the closed stage vocabulary — prepare is "dispatch", the mesh step
#: is "collective"; nothing else ever reaches _observe
_STAGE_NAMES = {"dispatch": "pipe.compute.dispatch",
                "collective": "pipe.compute.collective"}
#: stage suffix -> (latency histogram, bytes counter); cached like
#: pipe._STAGE_INSTRUMENTS — a rare double-create just wins the same
#: registry entry.
_INSTRUMENTS: dict = {}


def _observe(kind: str, seconds: float, nbytes: int, mesh: Mesh) -> None:
    """Fold one prepare ("dispatch") or step ("collective") measurement
    into the module totals, the shared ``request_stage_seconds{stage=
    pipe.compute.<kind>}`` tracing series (the PR 6 pipeline split),
    and the per-axis ``seaweed_mesh_axis_size`` gauges."""
    from ..util import tracing
    tup = _INSTRUMENTS.get(kind)
    if tup is None:
        stage = _STAGE_NAMES[kind]
        tup = (tracing.METRICS.histogram("request_stage_seconds",
                                         stage=stage),
               tracing.METRICS.counter("stage_bytes_total",
                                       stage=stage))
        _INSTRUMENTS[kind] = tup
    tup[0].observe(seconds)
    if nbytes:
        tup[1].inc(nbytes)
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    shape_changed = False
    with _STATS_LOCK:
        _TOTALS[f"{kind}_seconds"] += seconds
        if kind == "dispatch":
            _TOTALS["batches"] += 1
            _TOTALS["bytes_in"] += nbytes
        else:
            _TOTALS["bytes_out"] += nbytes
        if (_LAST_SHAPE["dp"], _LAST_SHAPE["sp"]) != (dp, sp):
            _LAST_SHAPE["dp"], _LAST_SHAPE["sp"] = dp, sp
            shape_changed = True
    if shape_changed:
        for axis, size in (("dp", dp), ("sp", sp)):
            tracing.METRICS.gauge("mesh_axis_size", axis=axis).set(size)


def debug_payload() -> dict:
    """``/debug/vars`` "mesh" section (util/varz.py): the configured
    shape plus the cumulative dispatch/collective split."""
    with _STATS_LOCK:
        out = {k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in _TOTALS.items()}
        out["axes"] = dict(_LAST_SHAPE)
        out["device_bytes_in"] = {str(d): n for d, n
                                  in sorted(_DEVICE_BYTES.items())}
    out["configured"] = {"enabled": _CONFIG.enabled,
                         "dp": _CONFIG.dp, "sp": _CONFIG.sp}
    return out


def reset_telemetry() -> None:
    """Drop the cumulative mesh-stage totals (tests)."""
    with _STATS_LOCK:
        for k in _TOTALS:
            _TOTALS[k] = 0 if isinstance(_TOTALS[k], int) else 0.0
        _LAST_SHAPE["dp"] = _LAST_SHAPE["sp"] = 0
        _DEVICE_BYTES.clear()


# --------------------------------------------------------------------------
# the production host-batch path: prepare (H2D) / apply (mesh step)
# --------------------------------------------------------------------------

class Prepared:
    """A host batch already placed on the mesh: the (possibly padded)
    async sharded device array plus the original (b, s) so apply can
    slice the padding back off lazily."""

    __slots__ = ("arr", "b", "s", "mesh")

    def __init__(self, arr, b: int, s: int, mesh: Mesh):
        self.arr = arr
        self.b = b
        self.s = s
        self.mesh = mesh


def _auto_mesh_for(b: int) -> Mesh:
    """The adaptive auto mesh: small B (the rebuild path streams B=1
    chunks) drops to an sp-only mesh so every device holds a stripe
    slice instead of (dp-1)/dp of them computing zero padding."""
    global _auto_n_devices
    n_dev = len(jax.devices())
    if _auto_n_devices != n_dev:
        _auto_meshes.clear()
        _auto_steps.clear()  # steps bake their mesh into shard_map
        # device-count memo: jax.devices() is stable per process, so
        # every writer stores the same value and a racing re-clear
        # only costs a mesh rebuild
        # seaweedlint: disable=SW801 — idempotent memo
        _auto_n_devices = n_dev
    dp_auto, _ = _auto_factor(n_dev)
    dp = dp_auto if b >= dp_auto else 1
    mesh = _auto_meshes.get(dp)
    if mesh is None:
        mesh = make_mesh(dp=dp)
        _auto_meshes[dp] = mesh
    return mesh


def _step_for(coefs: np.ndarray, mesh: Mesh):
    """LRU-cached apply-only step for (mesh shape, coefs). Keyed by
    shape, not Mesh identity: every mesh here spans all local devices
    in enumeration order, so equal shapes are interchangeable."""
    key = (mesh.shape["dp"], mesh.shape["sp"],
           coefs.shape, coefs.tobytes())
    step = _auto_steps.get(key)
    if step is None:
        step = _make_apply_only_step(coefs, mesh)
        _auto_steps[key] = step
        while len(_auto_steps) > _AUTO_STEPS_CAP:
            _auto_steps.popitem(last=False)
    else:
        _auto_steps.move_to_end(key)
    return step


def prepare_batch(batch: np.ndarray, mesh=None) -> Prepared:
    """Pad a HOST (B, n_in, S) u8 batch to the mesh geometry and start
    its H2D transfer with (dp, -, sp) NamedSharding.

    Rows pad to the dp multiple and S to the kernel granule — zero
    rows/columns map to zero output and are sliced off lazily by
    :func:`apply_prepared`. With ``mesh=None`` (or :data:`AUTO`) the
    adaptive auto mesh is used; an explicit Mesh is honored AS GIVEN —
    an uneven batch pads rather than re-factoring the mesh. The
    placement time lands in the ``pipe.compute.dispatch`` stage, which
    is what ``[pipeline] double_buffer`` overlaps with the previous
    batch's collective."""
    from ..pipeline import flight
    # the span's end means the async device_put is ISSUED (transfer in
    # flight), not landed — the landing is observed by the batch's
    # sync span
    with flight.span("h2d_submit") as span:
        b, n_in, s = batch.shape
        if mesh is None or mesh is AUTO:
            mesh = _auto_mesh_for(b)
        dp = mesh.shape["dp"]
        sp = mesh.shape["sp"]
        gran = _granule(sp)
        b_pad = -(-b // dp) * dp
        s_pad = -(-s // gran) * gran
        if b_pad != b or s_pad != s:
            padded = np.zeros((b_pad, n_in, s_pad), dtype=np.uint8)
            padded[:b, :, :s] = batch
            batch = padded
        arr = shard_batch(batch, mesh)
        with _STATS_LOCK:
            for shard in arr.addressable_shards:
                _DEVICE_BYTES[shard.device.id] = _DEVICE_BYTES.get(
                    shard.device.id, 0) + shard.data.nbytes
        span.nbytes = batch.nbytes
    _observe("dispatch", span.elapsed, batch.nbytes, mesh)
    return Prepared(arr, b, s, mesh)


def apply_prepared(coefs: np.ndarray, prep: Prepared):
    """Apply coefficient rows to a prepared (sharded) batch; returns
    the async device (b, n_out, s) result sliced back to the original
    extents (np.asarray materializes it — callers in the 3-stage
    pipeline keep their D2H on the writer thread). The step-enqueue
    time lands in the ``pipe.compute.collective`` stage."""
    from ..pipeline import flight
    with flight.span("launch", nbytes=prep.arr.nbytes) as span:
        coefs = np.ascontiguousarray(coefs, dtype=np.uint8)
        step = _step_for(coefs, prep.mesh)
        rs_jax.count_leg("device" if _real_accelerator() else "xla",
                         prep.arr.nbytes)
        out = step(prep.arr)[:prep.b, :, :prep.s]  # lazy slice; no sync
    _observe("collective", span.elapsed, out.nbytes, prep.mesh)
    return out


def encode_step_fns(encoder: Encoder, mesh=None):
    """(prepare_fn, apply_fn) pair for the pipeline's split compute
    stage (pipe.run_pipeline's ``prepare_fn``): prepare starts the H2D
    shard placement, apply runs the mesh parity step on the prepared
    array — the split that lets ``[pipeline] double_buffer`` overlap
    the next batch's transfer with the current batch's collective."""
    coefs = encoder.parity_coefs

    def prep(batch: np.ndarray) -> Prepared:
        return prepare_batch(batch, mesh)

    def apply(prepared: Prepared):
        return apply_prepared(coefs, prepared)

    return prep, apply


def _apply_host_sharded(coefs: np.ndarray, batch: np.ndarray, mesh=None):
    """Apply coefficient rows to a HOST (B, n_in, S) u8 batch over a
    mesh spanning ALL local devices; returns an async device
    (B, n_out, S) result. ``mesh=None``/:data:`AUTO` adapts the mesh
    to the batch; an explicit Mesh is honored as given (rows pad, the
    mesh never silently re-factors). The prepare/apply split is the
    same one the pipeline uses for double buffering."""
    return apply_prepared(coefs, prepare_batch(batch, mesh))


def encode_parity_host_sharded(encoder: Encoder, batch: np.ndarray,
                               mesh=None):
    """Production multi-chip encode: HOST (B, k, S) u8 -> async
    (B, m, S) parity over all local devices. This is the entry the
    coalescing batcher uses when routing_mesh() says to shard — the
    8-device CPU mesh in tests, the driver's dryrun, an explicit
    [mesh]/-mesh config, and real multi-chip accelerators (a
    single-chip host never takes it). ``mesh``: None/AUTO for
    the adaptive auto mesh, or the explicit Mesh to honor."""
    return _apply_host_sharded(encoder.parity_coefs, batch, mesh)


def reconstruct_host_sharded(encoder: Encoder, survivors: np.ndarray,
                             present, wanted, mesh=None):
    """Production multi-chip rebuild: decode rows for (present ->
    wanted) applied to HOST survivor chunks over the whole mesh — the
    multi-device form of reconstruct_batch_host that the rebuild
    pipeline uses when routing_mesh() says to shard. ``survivors``:
    (B, len(present), S) u8, first k used. ``mesh`` as in
    :func:`encode_parity_host_sharded`."""
    rows = encoder.decode_matrix_rows(list(present), list(wanted))
    chosen = survivors[:, :encoder.data_shards, :]
    if not chosen.flags.c_contiguous:
        chosen = np.ascontiguousarray(chosen)
    return _apply_host_sharded(rows, chosen, mesh)


def shard_batch(x: np.ndarray, mesh: Mesh, pad: bool = False):
    """Device-put a (B, k, S) batch with (dp, -, sp) sharding.

    Validates divisibility (rows must divide dp; S per chip must stay
    a multiple of the 128-byte packing group) — or, with ``pad=True``,
    zero-pads the row axis to the dp multiple and S to the sp*group
    granule instead (zero rows/columns encode to zero output; callers
    slice by the ORIGINAL extents, as prepare_batch/apply_prepared
    do)."""
    dp = mesh.shape["dp"]
    sp = mesh.shape["sp"]
    b, n_in, s = x.shape
    if pad and (b % dp or s % (sp * GROUP)):
        b_pad = -(-b // dp) * dp
        s_pad = -(-s // (sp * GROUP)) * (sp * GROUP)
        padded = np.zeros((b_pad, n_in, s_pad), dtype=np.uint8)
        padded[:b, :, :s] = x
        x = padded
        b, s = b_pad, s_pad
    if b % dp:
        raise ValueError(f"batch {b} not divisible by dp={dp}")
    if s % (sp * GROUP):
        raise ValueError(
            f"shard length {s} not divisible by sp*{GROUP} = {sp * GROUP}")
    sharding = NamedSharding(mesh, P("dp", None, "sp"))
    return jax.device_put(x, sharding)
