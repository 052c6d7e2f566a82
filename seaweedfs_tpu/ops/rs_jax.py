"""Batched Reed-Solomon codec: the klauspost ``Encoder`` method set over
three legs, and the one place that chooses between them.

This is the replacement for the reference's
``klauspost/reedsolomon.Encoder`` (SURVEY.md §2 L0): the same method
surface as ops/rs_ref.py, over batched ``(B, k, S)`` uint8 arrays. One
``Encoder`` instance serves any batch size; jit's own cache holds the
shapes. The legs, as /debug/vars ("codec") counts their bytes:

* ``device`` — the Pallas kernel of ops/rs_pallas.py, on a TPU, for
  shards of at least PALLAS_MIN_S bytes. A HOST slab whose S conforms
  to the kernel's segment is viewed (zero-copy) in word form and goes
  through apply_matrix_host_multi, grouped up to host_dispatch_group()
  slabs per dispatch: every EC pipeline's path. An encode runs
  ``rs_words``: the parity matrix is a constant of the codec, built
  into the program with its XOR network factored. A reconstruct runs
  ``rs_words_mat``: the decode matrix follows the shards that were
  lost, so it is an ARGUMENT of the program (DecodeMatrix.operand),
  and a process compiles one decode program per (rows wanted, chunk
  shape, group width), whatever the loss patterns it meets.
  Anything else that reaches the device — a tail that does not conform,
  a device-resident array, the mesh route — goes through apply_matrix
  to the u8 entry ``rs_u8``, which pads S and relays the bytes out on
  the device.
* ``native`` — the host SIMD codec (ops/rs_native.py): every payload
  shorter than PALLAS_MIN_S on every backend, and large host slabs when
  SEAWEEDFS_TPU_HOST_DISPATCH keeps them off the link ("native", or
  "auto" with a link slower than the codec).
* ``xla`` — the bitslice network of ops/bitslice.py under plain XLA: a
  backend with neither a TPU nor the native codec, and device-resident
  arrays too short for the kernel.

Reconstruction follows klauspost ``reconstruct`` semantics: take the first
k surviving shard indices, invert those k rows of the code matrix on the
host (tiny GF(2^8) Gauss-Jordan), and apply the needed rows through the
same legs as encode. The inverted matrices are memoized per survivor
set, mirroring klauspost's inversion_tree.go cache: host data, a
hundred bytes each. What is keyed by a matrix's bytes and compiles per
matrix is the u8 entry alone (apply_matrix: tails that do not conform,
device-resident arrays, the mesh) and the encode programs, whose one
matrix never changes.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..pipeline import flight
from . import bitslice, gf256, rs_native, rs_pallas
from .rs_ref import ShardSizeError, TooFewShardsError

GROUP = bitslice.GROUP_BYTES

#: Use the fused Pallas kernel on TPU once a shard is at least this long
#: (below it, the pad to rs_pallas.SEG_BYTES and grid overhead dominate).
PALLAS_MIN_S = 256 * 1024
#: Chunk the pure-XLA path along S above this, bounding the ~12x word
#: expansion its unfused pack/XOR/unpack intermediates cost in HBM/RAM.
XLA_CHUNK_S = 4 * 1024 * 1024
#: Test/debug override: "pallas" | "native" | "xla" | None (auto).
FORCE: Optional[str] = None
#: Hybrid policy, part 2 (large HOST payloads): "auto" measures the
#: host->device link and the native codec once and sends host-resident
#: slabs to the device only when the link can stream bytes faster than
#: the host codec computes them (otherwise the transfer alone loses
#: the race). The verdict is logged once and shown at /debug/vars
#: ("codec"). "device" / "native" pin the choice.
HOST_DISPATCH = os.environ.get("SEAWEEDFS_TPU_HOST_DISPATCH", "auto")
#: How many equally-shaped host slabs one device dispatch may carry on
#: the word-form path (apply_matrix_host_multi): one jitted call over
#: several slab-sized args pays the per-dispatch launch+sync floor
#: once for the whole group. A power of two: runs split into
#: power-of-two widths, so the jit cache holds log2 of this per shape.
DISPATCH_GROUP = 16
_link_gibps: Optional[float] = None
_native_gibps: Optional[float] = None
_calibrate_lock = threading.Lock()

#: Where compiled programs persist when nobody placed the cache from
#: outside: a FIXED path under the checkout (the path is part of the
#: cache key's environment — a directory that moves never hits).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def _place_compile_cache() -> None:
    """Every process that compiles imports this module first, so this
    runs once before any compile: ``JAX_COMPILATION_CACHE_DIR`` set
    means JAX already reads it and nothing is touched; otherwise the
    cache goes to COMPILE_CACHE_DIR. No other code sets a cache
    directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(COMPILE_CACHE_DIR))


_place_compile_cache()

#: Input bytes each codec leg has computed in this process: "device"
#: (Pallas kernel), "native" (host SIMD codec), "xla" (bitslice
#: network). Counted where the leg is decided —
#: apply_matrix_host_multi, apply_matrix and the mesh step — and
#: served at /debug/vars ("codec"): the hybrid policy may keep an
#: encode on the host with the chip idle, and nothing else says so.
_leg_bytes = {"device": 0, "native": 0, "xla": 0}
#: Times a jitted codec step of this module was traced (every trace is
#: a program compiled or fetched from the compile cache), and the
#: distinct (survivors, wanted) sets Encoder.decode_matrix was asked
#: for: a second loss pattern must add to the second and not the first.
_programs_traced = 0
_decode_patterns: set = set()
_leg_lock = threading.Lock()


@functools.lru_cache(maxsize=1)
def _device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def count_leg(leg: str, nbytes: int) -> None:
    with _leg_lock:
        _leg_bytes[leg] += int(nbytes)


def _count_trace() -> None:
    """Called from the body of a step as JAX traces it."""
    global _programs_traced
    with _leg_lock:
        _programs_traced += 1


def debug_payload() -> dict:
    """``/debug/vars`` "codec" section (util/varz.py). ``device`` stays
    None until :func:`backend` has run: serving a debug page must not
    be what initialises (and claims) the accelerator."""
    with _leg_lock:
        legs = dict(_leg_bytes)
        traced, patterns = _programs_traced, len(_decode_patterns)
    link, native = _link_gibps, _native_gibps
    if link is None or native is None:
        choice = None
    else:
        choice = "device" if link > native else "host"
    return {
        "device": dict(_device_info())
        if _device_info.cache_info().currsize else None,
        "leg_bytes": legs,
        "programs_traced": traced,
        "decode_patterns": patterns,
        "host_dispatch": HOST_DISPATCH,
        "link_gibps": link,
        "native_gibps": native,
        "auto_choice": choice,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def _dispatch_mode() -> str:
    """Validated HOST_DISPATCH, checked at use time rather than at import
    so that a typo'd variable surfaces as a normal error from the encode
    call, not a bare traceback from every CLI entry point that
    transitively imports this module."""
    if HOST_DISPATCH not in ("auto", "device", "native"):
        raise ValueError(
            f"SEAWEEDFS_TPU_HOST_DISPATCH={HOST_DISPATCH!r}: expected "
            f"'auto', 'device' or 'native'")
    return HOST_DISPATCH


_donation_warning_squelched = False


def donation_enabled() -> bool:
    """Donate the freshly transferred word-form args to the jitted call
    (jax.jit donate_argnums)? On a TPU, yes: XLA frees each input inside
    the computation instead of holding input and output live together —
    a streaming encode keeps up to group x batch slabs in flight, so
    without donation peak HBM is roughly double the working set. On CPU,
    never: jnp.asarray may ALIAS the host numpy buffer (no transfer
    happens), and donating an aliased buffer would hand the pooled batch
    the writer still references to XLA as scratch. The mesh plane
    (parallel/mesh) donates its device_put shards under the same rule.

    Donation that XLA cannot alias (parity output is m/k the input
    size) still gives that early release, but JAX warns about every such
    call, so the warning is squelched once when donation first engages.
    """
    # deliberately the RAW backend, not _use_pallas(): tests monkeypatch
    # that predicate to force the device path on CPU (interpret-mode
    # kernels), and donating there is exactly the aliasing hazard above
    on = jax.default_backend() == "tpu"
    if on:
        global _donation_warning_squelched
        if not _donation_warning_squelched:
            import warnings
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            # idempotent one-way latch: racing writers both set True;
            # double-filtering a warning is harmless
            # seaweedlint: disable=SW801 — idempotent latch
            _donation_warning_squelched = True
    return on


class BackendUnavailable(RuntimeError):
    """The JAX backend this process was told to use did not start."""


def backend() -> str:
    """The platform JAX computes on; the first call initialises the
    backend and, on a TPU host, claims the chip for this process.

    A chip belongs to one process at a time. A second process that
    wants it fails at once inside libtpu, with an error that advises
    removing libtpu's lock file — the wrong advice on a host where
    another server rightly holds the chip — so the failure is re-raised
    saying what an operator can do about it."""
    try:
        name = jax.default_backend()
        _device_info()  # the backend is up: note what it is
        return name
    except RuntimeError as e:
        raise BackendUnavailable(
            f"cannot initialise the JAX backend: {e}\n"
            f"An accelerator belongs to ONE process at a time: a host "
            f"runs one chip-owning volume server per chip. If another "
            f"process holds the chip, stop it, or start this one with "
            f"JAX_PLATFORMS=cpu (host codec only). Do not remove "
            f"libtpu's lock file while its owner is alive.") from e


def _use_pallas() -> bool:
    # Mosaic kernels lower only for TPU; GPU/CPU take the XLA bitslice
    # network.
    return backend() == "tpu"


def _pick_variant(s: int) -> str:
    if FORCE:
        return FORCE
    _dispatch_mode()  # validate the env knob on EVERY backend, not just
    # TPU — a typo must not ride silently through CPU runs into a
    # deployment
    if _use_pallas() and s >= PALLAS_MIN_S:
        return "pallas"
    if rs_native.available():
        # Hybrid policy, part 1 (sub-slab work): below PALLAS_MIN_S the
        # dispatch+grid overhead beats any device win, so small
        # payloads take the SIMD codec on the host on EVERY backend — a
        # 4 KiB interval repair must never pay a device round trip (the
        # reference serves them from klauspost's SIMD loop for the same
        # reason).
        return "native"
    return "xla"


def _measure_link_gibps(n_bytes: int = 8 * 1024 * 1024) -> float:
    """One-time h2d+d2h round-trip bandwidth probe (GiB/s of payload
    moved per second of wall time, both directions counted)."""
    x = np.zeros(n_bytes, dtype=np.uint8)
    t0 = time.perf_counter()
    d = jax.device_put(x)
    jax.block_until_ready(d)
    np.asarray(d)
    dt = time.perf_counter() - t0
    return 2 * n_bytes / (1024 ** 3) / max(dt, 1e-9)


def _measure_native_gibps(n_bytes: int = 16 * 1024 * 1024) -> float:
    """One-time host-codec throughput probe (input GiB/s)."""
    k = 10
    coefs = gf256.build_code_matrix(k, k + 4)[k:]
    x = np.zeros((k, n_bytes // k), dtype=np.uint8)
    rs_native.apply_gf_matrix(coefs, x)  # warm: builds .so + tables
    t0 = time.perf_counter()
    rs_native.apply_gf_matrix(coefs, x)
    dt = time.perf_counter() - t0
    return x.size / (1024 ** 3) / max(dt, 1e-9)


def _device_worth_it() -> bool:
    """Hybrid policy, part 2: should a large HOST payload cross to the
    device? Probes both bandwidths once; the device wins only when the
    link outruns the host codec (see HOST_DISPATCH)."""
    mode = _dispatch_mode()
    if mode == "device":
        return True
    if mode == "native":
        return False
    if not rs_native.available():
        return True
    global _link_gibps, _native_gibps
    if _link_gibps is None:
        with _calibrate_lock:
            # re-check under the lock: concurrent callers (the repair
            # aggregator + a bulk decode run in parallel by design)
            # must neither double-probe nor share the link with each
            # other's probe — that would cache a distorted verdict for
            # the process lifetime
            if _link_gibps is None:
                link = _measure_link_gibps()
                # The probe may trigger the one-time native build; the
                # calibrate lock exists to single-fly exactly that.
                # seaweedlint: disable=SW103 — intentional build-once
                _native_gibps = _measure_native_gibps()
                _link_gibps = link
                from ..util import glog
                glog.info("rs dispatch calibration: link %.3f GiB/s, "
                          "native codec %.3f GiB/s -> large host slabs "
                          "%s", _link_gibps, _native_gibps,
                          "cross to the device"
                          if _link_gibps > _native_gibps
                          else "stay on the host codec (chip idle)")
    return _link_gibps > _native_gibps


@functools.lru_cache(maxsize=256)
def _jitted_apply(coefs_bytes: bytes, n_out: int, n_in: int, variant: str,
                  donate: bool = False):
    """One jitted executable per (coefficient matrix, backend variant);
    shapes stay polymorphic via jit's own shape cache. ``donate`` hands
    the input buffer to XLA (host word-form call sites only — they pass
    a freshly transferred device copy nothing else references)."""
    coefs = np.frombuffer(coefs_bytes, dtype=np.uint8).reshape(n_out, n_in)

    if variant == "pallas":
        def apply_fn(x: jnp.ndarray) -> jnp.ndarray:
            _count_trace()
            return rs_pallas.apply_gf_matrix(coefs, x)
    elif variant == "pallas_words":
        def apply_fn(x4: jnp.ndarray) -> jnp.ndarray:
            _count_trace()
            return rs_pallas.apply_gf_matrix_words(coefs, x4)
    elif variant == "xla":
        def apply_fn(x: jnp.ndarray) -> jnp.ndarray:
            _count_trace()
            return bitslice.apply_gf_matrix(coefs, x)
    else:  # "xla_chunked": x is (B, n_in, nc, sc)
        def apply_fn(x: jnp.ndarray) -> jnp.ndarray:
            _count_trace()
            # lax.map over column chunks keeps live intermediates to one
            # chunk's worth while XLA still fuses within each step.
            xc = x.transpose(2, 0, 1, 3)
            yc = jax.lax.map(
                lambda v: bitslice.apply_gf_matrix(coefs, v), xc)
            return yc.transpose(1, 2, 0, 3)

    _name_step(apply_fn, variant, 1)
    return jax.jit(apply_fn, donate_argnums=(0,)) if donate \
        else jax.jit(apply_fn)


def _name_step(fn, variant: str, width: int) -> None:
    """Name a step for the trace before it is jitted: the entry point
    and the group width (``rs_pallas_words_g8``) in place of
    ``apply_fn``, so that a device operation says which program it
    belongs to. Names only: the computation is the same."""
    fn.__name__ = fn.__qualname__ = f"rs_{variant}_g{width}"


@functools.lru_cache(maxsize=64)
def _jitted_apply_multi(coefs_bytes: bytes, n_out: int, n_in: int,
                        nargs: int, donate: bool = False):
    """One jitted executable per (coefficient matrix, group width):
    nargs word-form slabs in, nargs parities out. One dispatch for the
    whole group, so the launch+sync floor is paid once per group instead
    of once per slab. ``donate`` hands every slab arg to XLA — the
    streaming pipeline's HBM high-water mark drops from (inputs +
    outputs) to one group of inputs, since each slab's buffer frees as
    the computation consumes it."""
    coefs = np.frombuffer(coefs_bytes, dtype=np.uint8).reshape(n_out, n_in)

    def apply_fn(*xs):
        assert len(xs) == nargs
        _count_trace()
        return tuple(rs_pallas.apply_gf_matrix_words(coefs, x) for x in xs)

    _name_step(apply_fn, "pallas_words", nargs)
    return jax.jit(apply_fn, donate_argnums=tuple(range(nargs))) \
        if donate else jax.jit(apply_fn)


@functools.lru_cache(maxsize=64)
def _jitted_apply_mat(n_out: int, n_in: int, nargs: int,
                      donate: bool = False):
    """The decode callers' executable, one per (matrix shape, group
    width) and none per matrix: the expanded matrix
    (rs_pallas.matrix_operand) is its first argument, nargs word-form
    slabs follow, nargs results come out. ``donate`` hands the slabs,
    and not the matrix, to XLA. The kernel sits in a jitted function of
    one slab that the step calls nargs times: JAX traces the kernel's
    body once for the program and not once per slab, and tracing is
    nearly all a new width costs (0.3 s against 3.0 s for sixteen
    slabs, lowered for a described v5e)."""
    @jax.jit
    def one_slab(mat, x4):
        return rs_pallas.apply_gf_matrix_words_mat(mat, x4, n_out)

    def apply_fn(mat, *xs):
        assert len(xs) == nargs
        _count_trace()
        return tuple(one_slab(mat, x) for x in xs)

    _name_step(apply_fn, "pallas_words_mat", nargs)
    return jax.jit(apply_fn, donate_argnums=tuple(range(1, nargs + 1))) \
        if donate else jax.jit(apply_fn)


def _submit(words: list) -> list:
    """The host side of H2D: hand each word-form slab to the runtime
    (the transfer itself goes on asynchronously on its threads)."""
    with flight.span("h2d_submit", nbytes=sum(w.nbytes for w in words)):
        return [jnp.asarray(w) for w in words]


def _launch(fn, xs: list, nbytes: int):
    """The jitted call itself, apart from the submit before it."""
    with flight.span("launch", nbytes=nbytes):
        return fn(*xs)


class _HostParity:
    """Async device parity held in word form; ``np.asarray`` (the
    pipeline writer's sync point) fetches it and re-views the bytes as
    (B, m, S) uint8 — a zero-copy host reshape. The writer takes that
    sync in steps, as a ``jax.Array`` offers them: ask for the fetch,
    wait until the result is ready on the device, then ``np.asarray``.
    ``launched`` is (the clock when the dispatch's jitted call
    returned, the dispatch's input bytes), one tuple shared by the
    results of one dispatch: with the time a result was found ready it
    says what rate the group's inputs crossed at."""

    __slots__ = ("dev", "b", "m", "s", "launched")

    def __init__(self, dev, b: int, m: int, s: int,
                 launched: Optional[tuple] = None):
        self.dev = dev
        self.b = b
        self.m = m
        self.s = s
        self.launched = launched

    def copy_to_host_async(self) -> None:
        self.dev.copy_to_host_async()

    def block_until_ready(self) -> None:
        self.dev.block_until_ready()

    def __array__(self, dtype=None, copy=None):
        w = np.asarray(self.dev)
        out = w.view(np.uint8).reshape(self.b, self.m, self.s)
        if dtype is not None and out.dtype != dtype:
            return out.astype(dtype)
        return out


def apply_matrix_host(coefs: np.ndarray, batch):
    """HOST (B, n_in, S) uint8 -> async result whose ``np.asarray``
    yields (B, n_out, S) uint8: apply_matrix_host_multi on a run of one
    (Encoder.encode_parity_host)."""
    return apply_matrix_host_multi(coefs, [batch])[0]


def _host_eligible(n_in: int, batch) -> bool:
    """THE host-slab device-dispatch eligibility rule: HOST-contiguous
    (B, n_in, S) uint8 with a Pallas-eligible S."""
    return (isinstance(batch, np.ndarray) and batch.ndim == 3
            and batch.dtype == np.uint8 and batch.flags.c_contiguous
            and FORCE is None and batch.shape[1] == n_in
            and _pick_variant(batch.shape[-1]) == "pallas")


def _stay_on_host() -> bool:
    """Hybrid rule, spelled once: large host slabs stay on the host
    when the link can't outrun the host codec (and the codec exists).
    (Pinned "native" without a built codec goes to the device leg
    instead of crashing.)"""
    return not _device_worth_it() and rs_native.available()


def host_dispatch_group() -> int:
    """Group width for the host-slab pipelines (ONE policy for encode
    and the coalescing batcher; a rebuild launches one slab a
    dispatch): DISPATCH_GROUP on a
    single-device accelerator backend, else 1 — multi-chip paths
    mesh-shard each batch instead (parallel/mesh), and CPU backends
    never take the word-form device path."""
    if not _use_pallas() or len(jax.devices()) > 1:
        return 1
    return DISPATCH_GROUP


def _host_word_form(batch: np.ndarray) -> np.ndarray:
    """Zero-copy view of a HOST (B, n_in, S) uint8 slab whose S conforms
    (rs_pallas.conforms) in the kernel's pre-tiled word form,
    (B, n_in, 32, R, 128) u32 — the array rs_pallas.apply_gf_matrix
    builds on the device with a bitcast and a reshape, here for free."""
    b, n_in, s = batch.shape
    return batch.view(np.uint32).reshape(
        b, n_in, rs_pallas.GROUP_WORDS,
        s // (4 * rs_pallas.GROUP_WORDS * rs_pallas.LANES),
        rs_pallas.LANES)


def apply_matrix_host_multi(coefs: np.ndarray, batches,
                            matrix: "Optional[DecodeMatrix]" = None):
    """A list of HOST (B, n_in, S) uint8 slabs -> a list of async
    results in the same order, each yielding (B, n_out, S) uint8 under
    ``np.asarray``. THE host-slab dispatch: every EC pipeline's encode
    and reconstruct call ends here.

    A slab the Pallas dispatch applies to (_host_eligible) stays on the
    host codec when the hybrid rule says so; otherwise, when its S
    conforms, it is VIEWED (zero-copy) in the kernel's word form and fed
    to the kernel — none of the XLA copy/reshape/broadcast glue of the
    u8 path. Everything else defers to apply_matrix.

    ``matrix`` is what tells a reconstruct from an encode: a decode
    caller hands over its DecodeMatrix (whose rows ``coefs`` are), and
    its word-form dispatches run the program that takes the expanded
    matrix as an argument (``rs_words_mat``, _jitted_apply_mat);
    without it ``coefs`` is built into the program (``rs_words``),
    which is right for the one matrix an encoder has.

    Runs of adjacent, identically-shaped word-form slabs are dispatched
    as ONE jitted call with up to DISPATCH_GROUP slab args, amortizing
    the per-dispatch launch+sync floor; a shape change or a full group
    flushes, and a flushed run is split into power-of-two
    sub-dispatches — so the jit cache sees at most log2(group) (shape,
    width) pairs per workload, never a retrace storm (the pipeline's
    greedy drain yields arbitrary run lengths). An encode's lone slab
    runs the single-slab executable (_jitted_apply)."""
    coefs = np.ascontiguousarray(coefs, dtype=np.uint8)
    n_out, n_in = coefs.shape
    key = (coefs.tobytes(), n_out, n_in)
    out: list = [None] * len(batches)
    stay_host: Optional[bool] = None
    run: list[int] = []

    def dispatch(ixs):
        nbytes = sum(batches[i].nbytes for i in ixs)
        count_leg("device", nbytes)
        words = [_host_word_form(batches[i]) for i in ixs]
        xs = _submit(words)
        if matrix is not None:
            fn = _jitted_apply_mat(n_out, n_in, len(ixs),
                                   donate=donation_enabled())
            ys = _launch(functools.partial(fn, matrix.on_device()), xs,
                         nbytes)
        elif len(ixs) == 1:
            fn = _jitted_apply(*key, "pallas_words",
                               donate=donation_enabled())
            ys = [_launch(fn, xs, nbytes)]
        else:
            fn = _jitted_apply_multi(*key, len(ixs),
                                     donate=donation_enabled())
            ys = _launch(fn, xs, nbytes)
        launched = (time.perf_counter(), nbytes)
        for i, y in zip(ixs, ys):
            b, _, s = batches[i].shape
            out[i] = _HostParity(y, b, n_out, s, launched)

    def flush():
        # quantize to power-of-two widths (13 -> 8+4+1) so executables
        # are shared across the drain's arbitrary run lengths
        pos = 0
        while pos < len(run):
            width = 1 << ((len(run) - pos).bit_length() - 1)
            dispatch(run[pos:pos + width])
            pos += width
        run.clear()

    for i, batch in enumerate(batches):
        eligible = _host_eligible(n_in, batch)
        if eligible and stay_host is None:
            stay_host = _stay_on_host()
        if eligible and stay_host:
            # link slower than the host codec: crossing can only lose,
            # through the word form or apply_matrix's padded u8 path
            flush()
            count_leg("native", batch.nbytes)
            out[i] = rs_native.apply_gf_matrix(coefs, batch)
        elif eligible and rs_pallas.conforms(batch.shape[-1]):
            if run and (batch.shape != batches[run[0]].shape
                        or len(run) >= DISPATCH_GROUP):
                flush()
            run.append(i)
        else:
            flush()
            out[i] = apply_matrix(coefs, batch)
    flush()
    return out


def apply_matrix(coefs: np.ndarray, x) -> "np.ndarray | jnp.ndarray":
    """Dispatch to the fused Pallas kernel (TPU) or the chunked XLA
    network, padding S to the chosen path's granularity and slicing back
    (zero bytes encode to zero parity, so padding is transparent).

    Returns a device array, EXCEPT on the native host-codec leg with a
    host numpy input, where the host-resident result is returned as
    plain numpy (uploading it would defeat the hybrid policy)."""
    coefs = np.ascontiguousarray(coefs, dtype=np.uint8)
    n_out, n_in = coefs.shape
    if getattr(x, "ndim", None) not in (2, 3):
        raise ValueError(
            f"expected (n_in, S) or (B, n_in, S), got {getattr(x, 'shape', x)}")
    squeeze = x.ndim == 2
    variant = _pick_variant(x.shape[-1])
    if variant == "native" and FORCE is None \
            and not isinstance(x, np.ndarray) \
            and jax.default_backend() != "cpu":
        # never DOWNLOAD a device-resident array just to use the host
        # codec — the hybrid policy only redirects host payloads. On
        # the CPU backend a jax.Array is already host memory, so the
        # (~10x faster) native codec stays the right choice there.
        variant = "xla"
    if variant == "native":
        # Stay on the host end to end — converting through a device
        # buffer first would add two full copies of the payload, and on
        # a non-CPU backend jnp.asarray would UPLOAD the result, so the
        # host-resident answer is returned as plain numpy.
        x = np.asarray(x, dtype=np.uint8)
        count_leg("native", x.nbytes)
        return rs_native.apply_gf_matrix(coefs, x)
    x = jnp.asarray(x, dtype=jnp.uint8)
    if squeeze:
        x = x[None]
    b, _, s = x.shape
    count_leg("device" if variant == "pallas" else "xla", x.size)
    nc = 1
    if variant == "pallas":
        seg = rs_pallas.SEG_BYTES
    elif variant == "xla" and s > XLA_CHUNK_S:
        variant = "xla_chunked"
        nc = -(-s // XLA_CHUNK_S)
        sc = -(-(-(-s // nc)) // GROUP) * GROUP  # ceil(s/nc) up to GROUP
        seg = nc * sc
    else:
        variant, seg = "xla", GROUP
    pad = (-s) % seg
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    if variant == "xla_chunked":
        x = x.reshape(b, n_in, nc, (s + pad) // nc)
    fn = _jitted_apply(coefs.tobytes(), n_out, n_in, variant)
    y = fn(x)
    if variant == "xla_chunked":
        y = y.reshape(b, n_out, s + pad)
    if pad:
        y = y[..., :s]
    return y[0] if squeeze else y


class DecodeMatrix:
    """The rows that rebuild the wanted shards from the first k
    survivors, in the two forms the legs take: ``rows``, (n_out, k)
    GF(2^8) coefficients (native, xla, the u8 entry), and ``operand``,
    the same matrix expanded for ``rs_words_mat``
    (rs_pallas.matrix_operand) — data, handed over with every dispatch,
    so that no program is built for this loss in particular."""

    __slots__ = ("rows", "operand", "_device")

    def __init__(self, rows: np.ndarray):
        self.rows = np.ascontiguousarray(rows, dtype=np.uint8)
        self.operand = rs_pallas.matrix_operand(self.rows)
        self._device = None

    def on_device(self):
        """``operand`` as a device array, transferred when the first
        device dispatch asks and kept: a run's later dispatches pass
        what is there (a host array would cross the link with each,
        ~1.7 ms a dispatch on the v5e host, PERF.md §6)."""
        if self._device is None:
            self._device = jnp.asarray(self.operand)
        return self._device

    def apply_host(self, shards):
        """HOST (B, >= k, S) uint8 survivors -> async (B, n_out, S)."""
        return self.apply_host_multi([shards])[0]

    def apply_host_multi(self, chunks):
        """A list of HOST survivor chunks -> a list of async rebuilt
        shards (apply_matrix_host_multi, the matrix as an argument)."""
        k = self.rows.shape[1]
        prepared = []
        for c in chunks:
            chosen = c[:, :k, :]
            if (isinstance(chosen, np.ndarray)
                    and not chosen.flags.c_contiguous):
                chosen = np.ascontiguousarray(chosen)
            prepared.append(chosen)
        return apply_matrix_host_multi(self.rows, prepared, matrix=self)


class Encoder:
    """Parametrized RS(k, m) with the klauspost Encoder method set,
    executing on whatever backend JAX targets (TPU v5e here; XLA:CPU is
    the no-device fallback, mirroring the reference's SIMD CPU path)."""

    def __init__(self, data_shards: int, parity_shards: int):
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("data_shards and parity_shards must be positive")
        if data_shards + parity_shards > 256:
            raise ValueError("at most 256 total shards in GF(2^8)")
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix = gf256.build_code_matrix(data_shards, self.total_shards)
        self._decode_cache: dict[tuple[int, ...], np.ndarray] = {}

    # -- batched array API (the TPU-native surface) -----------------------

    @property
    def parity_coefs(self) -> np.ndarray:
        """(m, k) uint8 parity rows of the code matrix, C-contiguous —
        the coefficients a caller hands to bitslice.apply_gf_matrix."""
        return np.ascontiguousarray(self.matrix[self.data_shards:],
                                    dtype=np.uint8)

    def encode_parity(self, data) -> jnp.ndarray:
        """data (B, k, S) or (k, S) uint8 -> parity (B, m, S) / (m, S)."""
        return apply_matrix(self.matrix[self.data_shards:], data)

    def encode_parity_host(self, batch):
        """Pipeline fast path: HOST (B, k, S) uint8 -> async parity
        whose ``np.asarray`` yields (B, m, S) uint8 — see
        apply_matrix_host."""
        return apply_matrix_host(self.matrix[self.data_shards:], batch)

    def encode_parity_host_multi(self, batches):
        """Grouped pipeline fast path: a list of HOST (B, k, S) uint8
        slabs -> a list of async parities, dispatching runs of
        same-shaped slabs as ONE device call (apply_matrix_host_multi)
        to amortize the per-dispatch floor."""
        return apply_matrix_host_multi(self.matrix[self.data_shards:],
                                       batches)

    def reconstruct_batch_host(self, shards, present: Sequence[int],
                               wanted: Optional[Sequence[int]] = None):
        """reconstruct_batch for HOST survivor arrays — rides the
        zero-relayout word-form path when eligible.
        ``shards``: (B, len(present), S) uint8 np array."""
        return self.decode_matrix(present, wanted).apply_host(shards)

    def reconstruct_batch_host_multi(self, chunks,
                                     present: Sequence[int],
                                     wanted: Optional[Sequence[int]]
                                     = None):
        """Grouped reconstruct_batch_host: a list of HOST
        (B, len(present), S) uint8 chunks sharing one survivor set ->
        a list of async rebuilt shards, with runs of same-shaped chunks
        dispatched as one device call (apply_matrix_host_multi). A
        caller with many lists for one loss (pipeline/rebuild.py) takes
        decode_matrix once and applies that."""
        return self.decode_matrix(present, wanted).apply_host_multi(chunks)

    def decode_matrix(self, present: Sequence[int],
                      wanted: Optional[Sequence[int]] = None
                      ) -> "DecodeMatrix":
        """The host's whole share of a reconstruct, once per loss:
        invert the survivors' rows, compose the parity rows wanted, and
        expand the result for the kernel (span ``decode_matrix``)."""
        with flight.span("decode_matrix"):
            return DecodeMatrix(self._decode_rows_for(present, wanted))

    def _decode_rows_for(self, present: Sequence[int],
                         wanted: Optional[Sequence[int]]) -> np.ndarray:
        """Shared front half of the reconstruct paths: default wanted
        to every missing shard and build the decode rows."""
        present = list(present)
        if wanted is None:
            missing = set(range(self.total_shards)) - set(present)
            wanted = sorted(missing)
        if not wanted:
            raise ValueError("nothing to reconstruct")
        rows = self.decode_matrix_rows(present, wanted)
        with _leg_lock:
            _decode_patterns.add((tuple(present[:self.data_shards]),
                                  tuple(wanted)))
        return rows

    def encode_batch(self, data) -> jnp.ndarray:
        """data (..., k, S) -> all shards (..., k+m, S) (data passthrough
        concatenated with computed parity)."""
        data = jnp.asarray(data, dtype=jnp.uint8)
        parity = self.encode_parity(data)
        return jnp.concatenate([data, parity], axis=-2)

    def verify_batch(self, shards) -> bool:
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        parity = self.encode_parity(shards[..., :self.data_shards, :])
        return bool(jnp.array_equal(parity,
                                    shards[..., self.data_shards:, :]))

    def decode_matrix_rows(self, present: Sequence[int],
                           wanted: Sequence[int]) -> np.ndarray:
        """Host-side: coefficient rows that rebuild ``wanted`` shards from
        the shards listed in ``present`` (first k of them are used).

        Rows for wanted data shard d come from the inverted submatrix; rows
        for wanted parity shard p are parity coefficients composed with the
        decode matrix (so parity can be rebuilt directly from survivors in
        ONE device pass, without materializing the data shards first —
        unlike the reference's two-step reconstruct).
        """
        present = tuple(present)
        if len(present) < self.data_shards:
            raise TooFewShardsError(
                f"need {self.data_shards} shards, have {len(present)}")
        chosen = present[:self.data_shards]
        decode = self._decode_cache.get(chosen)
        if decode is None:
            decode = gf256.gf_matrix_invert(self.matrix[list(chosen), :])
            self._decode_cache[chosen] = decode
        rows = []
        for w in wanted:
            if w < self.data_shards:
                rows.append(decode[w])
            else:
                # parity row in terms of data = matrix[w]; in terms of the
                # chosen survivors = matrix[w] @ decode.
                rows.append(gf256.gf_matmul(self.matrix[w][None, :],
                                            decode)[0])
        return np.stack(rows, axis=0)

    def reconstruct_batch(self, shards, present: Sequence[int],
                          wanted: Optional[Sequence[int]] = None):
        """Rebuild shards on-device.

        ``shards``: (B, len(present), S) uint8 — ONLY the surviving shards,
        ordered to match ``present``. ``wanted``: which absolute shard ids
        to produce (default: every missing one). Returns (B, len(wanted), S).
        """
        rows = self._decode_rows_for(present, wanted)
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        chosen = shards[..., :self.data_shards, :]
        return apply_matrix(rows, chosen)

    # -- klauspost-style in-place list API (drop-in for the oracle) -------

    def encode(self, shards: list) -> None:
        if len(shards) != self.total_shards:
            raise ShardSizeError(
                f"expected {self.total_shards} shards, got {len(shards)}")
        sizes = {len(s) for s in shards}
        if len(sizes) != 1:
            raise ShardSizeError("shards have inconsistent sizes")
        data = jnp.stack([jnp.asarray(s, dtype=jnp.uint8)
                          for s in shards[:self.data_shards]])
        parity = np.asarray(self.encode_parity(data))
        for i in range(self.parity_shards):
            shards[self.data_shards + i][:] = parity[i]

    def verify(self, shards: Sequence) -> bool:
        arr = jnp.stack([jnp.asarray(s, dtype=jnp.uint8) for s in shards])
        return self.verify_batch(arr)

    def reconstruct(self, shards: list, data_only: bool = False) -> None:
        if len(shards) != self.total_shards:
            raise ShardSizeError(
                f"expected {self.total_shards} shards, got {len(shards)}")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) == self.total_shards:
            return
        wanted = [i for i, s in enumerate(shards) if s is None
                  and (not data_only or i < self.data_shards)]
        if not wanted:
            return
        surv = jnp.stack([jnp.asarray(shards[i], dtype=jnp.uint8)
                          for i in present])
        rebuilt = np.asarray(self.reconstruct_batch(surv[None], present,
                                                    wanted))[0]
        for i, buf in zip(wanted, rebuilt):
            shards[i] = buf

    def reconstruct_data(self, shards: list) -> None:
        self.reconstruct(shards, data_only=True)

    def split(self, data) -> list:
        """klauspost ``Split``: one buffer -> k data shards (last
        zero-padded) + m zeroed parity shards, ready for encode()."""
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else data.astype(np.uint8)
        if buf.size == 0:
            raise ShardSizeError("cannot split empty buffer")
        per = -(-buf.size // self.data_shards)
        padded = np.zeros(per * self.data_shards, dtype=np.uint8)
        padded[:buf.size] = buf
        shards = [padded[i * per:(i + 1) * per].copy()
                  for i in range(self.data_shards)]
        shards += [np.zeros(per, dtype=np.uint8)
                   for _ in range(self.parity_shards)]
        return shards

    def join(self, shards: Sequence, size: int) -> bytes:
        """klauspost ``Join``: concatenate the k data shards, trim to
        ``size``."""
        if len(shards) < self.data_shards:
            raise TooFewShardsError("join needs all data shards")
        cat = np.concatenate([np.asarray(s, dtype=np.uint8)
                              for s in shards[:self.data_shards]])
        if cat.size < size:
            raise ShardSizeError("shards shorter than requested size")
        return cat[:size].tobytes()
