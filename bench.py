"""Benchmark: RS(10,4) encode throughput on the available accelerator.

Prints ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": "GiB/s", "vs_baseline": N, ...}

``vs_baseline`` is measured against the BASELINE.md target of 20 GiB/s
RS(10,4) encode per chip (BASELINE.json north star). Sub-metrics (rebuild,
end-to-end file path, alternate geometries, CPU baseline) ride in the same
JSON under ``extras`` and are echoed to stderr.

Measurement honesty (see PERF.md):
* The headline races (kernel x slabs-per-dispatch x input form)
  candidates over ~1 GiB of uploaded 160 MiB slabs — never one giant
  ``pallas_call``; multi-arg dispatches of slab-sized args carry more
  bytes per dispatch. Word-form candidates feed pre-tiled u32 arrays
  so no XLA relayout rides the timed path. On compile failure the slab
  auto-shrinks (halves) and retries.
* Every timed loop XOR-folds a checksum of each output ON DEVICE inside
  the same executable (accumulator threaded through the jit) and
  fetches the accumulator bytes at the end of the window — the clock
  stops only when real result bytes reached the host, so an
  early-return ``block_until_ready`` cannot fake the number. Distinct
  input buffers are used across calls so no result can be cached, and
  every candidate's checksum must match the oracle-smoked reference
  kernel's before its number can count.
* Device-resident (compute-only) and host->device->host (end-to-end) are
  measured separately; the e2e number is the link-bound figure
  SURVEY.md §7 hard-part-1 predicts.
* A real-device correctness smoke (encode + 2-shard reconstruct vs the
  NumPy oracle) gates the headline: if the kernel is wrong on the actual
  backend, the child aborts rather than report a throughput.

One process for each chip: the parent imports NO jax. Sub-benches run
one after another in SEPARATE children under a watchdog and append
their results to ``artifacts/BENCH_partial.jsonl`` as they complete;
the final JSON is a merge. The device stages (core / config3 / config5
/ mesh) need a TPU: one that finds none, or fails, ends the run with a
nonzero exit and no result line. The overhead stages measure host
planes on a mini cluster and run with ``JAX_PLATFORMS=cpu`` by design.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

TARGET_GIBPS = 20.0
GIB = 1024 ** 3
MIB = 1024 ** 2

CORE_TIMEOUT = 1500
CFG3_TIMEOUT = 480
CFG5_TIMEOUT = 420
CACHE_TIMEOUT = 180      # chunk-cache zipfian stage (pure CPU, no jax)
TRACE_TIMEOUT = 300      # tracing-overhead stage (CPU mini cluster)
TELEMETRY_TIMEOUT = 300  # telemetry-overhead stage (CPU mini cluster)
FAULT_TIMEOUT = 300      # fault-point-overhead stage (CPU mini cluster)
PROFILE_TIMEOUT = 300    # profiler-overhead stage (CPU mini cluster)
USAGE_TIMEOUT = 300      # usage-accounting-overhead stage (CPU mini cluster)
JOBS_TIMEOUT = 300       # maintenance-plane-overhead stage (CPU mini cluster)
INGRESS_TIMEOUT = 300    # ingress-admission-overhead stage (CPU mini cluster)
SCRUB_TIMEOUT = 300      # paced-scrub-overhead stage (CPU mini cluster)
SIM_TIMEOUT = 300        # cluster-at-scale sim stage (in-process master)
CKPT_TIMEOUT = 600       # checkpoint/dataloader stage (CPU mini cluster)
MESH_TIMEOUT = 600       # sharded-mesh encode/rebuild stage (docs/mesh.md)
FLIGHT_TIMEOUT = 900     # flight-recorder overhead stage (paired encodes)
RACECHECK_TIMEOUT = 900  # lockset race-checker overhead stage (paired encodes)
STREAM_STAGES_TIMEOUT = 300  # recorder-decomposed stream breakdown
SELF = os.path.abspath(__file__)
REPO = os.path.dirname(SELF)
ARTIFACTS = os.path.join(REPO, "artifacts")
PARTIAL = os.path.join(ARTIFACTS, "BENCH_partial.jsonl")

#: Starting per-shard slab length for the headline stream. 16 MiB/shard
#: = 160 MiB input per call.
SLAB_S0 = 16 * MIB
SLAB_MIN_S = 2 * MIB


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# parent-side process management (stdlib only — jax is never imported here)
# --------------------------------------------------------------------------

def _ambient_env() -> dict:
    env = dict(os.environ)
    pp = env.get("PYTHONPATH", "").split(os.pathsep)
    if REPO not in pp:
        env["PYTHONPATH"] = os.pathsep.join([REPO] + [p for p in pp if p])
    return env


def _cpu_env(n_cpu_devices: int = 0) -> dict:
    """For the stages that measure host planes, not the chip: the CPU
    backend, and exactly ``n_cpu_devices`` virtual devices when asked
    (replacing any ambient count)."""
    env = _ambient_env()
    env["JAX_PLATFORMS"] = "cpu"
    if n_cpu_devices > 1:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+\s*",
                       "", env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{n_cpu_devices}").strip()
    return env


def _run(args: list, env: dict, timeout: int):
    """Run a child, streaming its stderr through; returns (rc, stdout)."""
    try:
        proc = subprocess.run(
            [sys.executable, SELF] + args, env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=sys.stderr,
            timeout=timeout, text=True)
        return proc.returncode, proc.stdout
    except subprocess.TimeoutExpired as e:
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode("utf-8", "replace")
        return -1, out or ""
    except Exception as e:  # noqa: BLE001 — parent must never die
        log(f"bench child failed to launch: {e}")
        return -2, ""


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _parse_result(out: str):
    """Last JSON dict on stdout = the stage's result (stage children
    print plain result dicts like {"headline_gibps": ...})."""
    for line in reversed((out or "").strip().splitlines()):
        try:
            obj = json.loads(line)
        except (ValueError, TypeError):
            continue
        if isinstance(obj, dict):
            return obj
    return None


def _read_partials() -> dict:
    """Merge every stage line the children persisted (later lines win)."""
    merged: dict = {}
    try:
        with open(PARTIAL, "r", encoding="utf-8") as f:
            for line in f:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict):
                    merged.update(obj)
    except OSError:
        pass
    return merged


def _run_device_stage(flag: str, timeout: int) -> None:
    """Run one stage that measures the chip. It needs a TPU: the child
    refuses any other backend (_require_tpu), and a stage that fails
    ends the whole run nonzero — there is no CPU stand-in for a device
    number."""
    rc, out = _run([flag], _ambient_env(), timeout)
    if rc != 0 or _parse_result(out) is None:
        sys.exit(f"bench: device stage {flag} failed (rc={rc}); "
                 f"no result")


def parent() -> None:
    os.makedirs(ARTIFACTS, exist_ok=True)
    try:
        os.remove(PARTIAL)
    except OSError:
        pass

    _run_device_stage("--child-core", CORE_TIMEOUT)
    _run_device_stage("--child-config3", CFG3_TIMEOUT)
    _run_device_stage("--child-config5", CFG5_TIMEOUT)

    # Everything from here to the mesh stage measures a host plane on a
    # CPU mini cluster, by design: the read-path cache, tracing,
    # telemetry, fault points, the profiler, usage accounting, the
    # maintenance plane, ingress admission, the paced scrub (ISSUE 20's
    # <5% bar), the flight recorder (ISSUE 17's <2% bar) with its
    # stream-stage breakdown, the race checker (ISSUE 18's <5% bar),
    # the master's control plane at simulated scale, and the
    # checkpoint/dataloader workload on 8 virtual devices.
    stage_ok = {}
    for stage, flag, timeout, n_dev in (
            ("cache", "--child-cache", CACHE_TIMEOUT, 0),
            ("trace", "--child-trace-overhead", TRACE_TIMEOUT, 0),
            ("telemetry", "--child-telemetry-overhead",
             TELEMETRY_TIMEOUT, 0),
            ("fault", "--child-fault-overhead", FAULT_TIMEOUT, 0),
            ("profile", "--child-profile-overhead", PROFILE_TIMEOUT, 0),
            ("usage", "--child-usage-overhead", USAGE_TIMEOUT, 0),
            ("jobs", "--child-jobs-overhead", JOBS_TIMEOUT, 0),
            ("ingress", "--child-ingress-overhead", INGRESS_TIMEOUT, 0),
            ("scrub", "--child-scrub-overhead", SCRUB_TIMEOUT, 0),
            ("flight", "--child-flight-overhead", FLIGHT_TIMEOUT, 0),
            ("stream_stages", "--child-stream-stages",
             STREAM_STAGES_TIMEOUT, 0),
            ("racecheck", "--child-racecheck-overhead",
             RACECHECK_TIMEOUT, 0),
            ("sim", "--child-sim", SIM_TIMEOUT, 0),
            ("ckpt", "--child-ckpt", CKPT_TIMEOUT, 8)):
        rc, out = _run([flag], _cpu_env(n_dev), timeout)
        stage_ok[stage] = rc == 0 and _parse_result(out) is not None

    # Sharded-mesh encode/rebuild (docs/mesh.md) on every local chip;
    # on a one-chip host the child says there is nothing to shard.
    _run_device_stage("--child-mesh", MESH_TIMEOUT)

    merged = _read_partials()
    extras = {k: v for k, v in merged.items()
              if k not in ("headline_gibps",)}
    for stage in ("core", "config3", "config5", "mesh"):
        extras[f"{stage}_platform"] = "tpu"
    for stage, ok in stage_ok.items():
        extras[f"{stage}_platform"] = "cpu" if ok else "failed"

    headline = merged.get("headline_gibps")
    if headline is None:
        sys.exit("bench: the core stage produced no headline number")
    emit({
        "metric": "rs_10_4_encode_1gib_device",
        "value": round(float(headline), 3),
        "unit": "GiB/s",
        "vs_baseline": round(float(headline) / TARGET_GIBPS, 3),
        "platform": "tpu",
        "extras": extras,
    })


# --------------------------------------------------------------------------
# child-side helpers (each stage runs under its own parent watchdog)
# --------------------------------------------------------------------------

def _persist(stage_results: dict) -> None:
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(PARTIAL, "a", encoding="utf-8") as f:
        f.write(json.dumps(stage_results) + "\n")


def _require_tpu() -> bool:
    """Device stages measure the chip or nothing: any other backend
    ends the child nonzero before it prints a number."""
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(
            f"bench: this stage needs a TPU; JAX's backend is "
            f"{platform!r}")
    return True


class _ChecksumTimer:
    """Times a sequence of device calls honestly: each output is XOR-folded
    into a tiny on-device accumulator, and the clock stops only when the
    accumulator's bytes are fetched to host (np.asarray). A backend whose
    block_until_ready returns early cannot fake this; distinct inputs per
    call prevent any result caching."""

    def __init__(self):
        import jax.numpy as jnp
        self._jnp = jnp
        self.acc = None
        self.t0 = None

    def start(self):
        self.acc = None
        self.t0 = time.perf_counter()

    def fold(self, y):
        tip = y[..., :256]
        flat = tip.reshape(-1, 256)
        piece = flat[0]
        self.acc = piece if self.acc is None else self.acc ^ piece

    def stop(self) -> float:
        import numpy as np
        np.asarray(self.acc)  # forces the whole dependency chain
        return time.perf_counter() - self.t0


def _make_slabs(n_bufs: int, k: int, s: int, seed: int = 0):
    """n distinct random host arrays of shape (1, k, s) uint8."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
            for _ in range(n_bufs)]


def _fold_checksum(y):
    """XOR-reduce an output to one (8, 128) u32 tile — used INSIDE jit.

    Every output byte feeds the reduction, so fetching the folded tile
    proves the whole encode ran; and because the fold lives in the same
    executable as the encode, a timed call costs ONE dispatch (folding
    via separate un-jitted ops would cost a dispatch each)."""
    import jax
    import jax.numpy as jnp
    yw = jax.lax.bitcast_convert_type(
        y.reshape(*y.shape[:-1], y.shape[-1] // 4, 4), jnp.uint32)
    return _fold_checksum_u32(yw)  # same fold order as the word forms


def _host_words(arr, form: str):
    """Zero-copy host view of a (B, k, S) u8 array in a kernel word
    form ("w4"/"w5"), using rs_pallas's own layout constants."""
    import numpy as np

    from seaweedfs_tpu.ops import rs_pallas
    b, k, sz = arr.shape
    w = sz // 4
    v = arr.view(np.uint32)  # C-contiguous; little-endian like bitcast
    if form == "w4":
        return v.reshape(b, k, w // rs_pallas.LANES, rs_pallas.LANES)
    return v.reshape(b, k, rs_pallas.GROUP_WORDS,
                     w // (rs_pallas.GROUP_WORDS * rs_pallas.LANES),
                     rs_pallas.LANES)


def _fold_checksum_u32(y):
    """_fold_checksum for outputs already in u32 word form: same fold
    order as the u8 variant (the word views flatten to the same u32
    sequence), so checksums are comparable across forms."""
    import jax.numpy as jnp
    return jnp.bitwise_xor.reduce(y.reshape(-1, 8, 128), axis=0)


def _make_folded_fn(gf, coefs, nargs: int, fold=_fold_checksum):
    """jit of: acc, slabs -> acc ^ fold(parity of each slab).

    One device dispatch per NARGS slabs: multiple slab-sized args
    amortize the per-dispatch cost that dominates single-slab calls.
    Threading the accumulator THROUGH the jit keeps the cross-call XOR
    chain on device without a separate eager dispatch per call."""
    import jax

    def f(acc, *xs):
        assert len(xs) == nargs, f"group width {len(xs)} != nargs {nargs}"
        for x in xs:
            acc = acc ^ fold(gf(coefs, x))
        return acc

    return jax.jit(f)


def _time_folded(fn, groups, passes: int) -> tuple[float, float]:
    """Honest wall time: warm pass first, then `passes` passes over all
    groups (distinct buffers), window closed by fetching the on-device
    XOR accumulator's bytes. Returns (timed_seconds, warm_seconds) —
    warm covers compile + first touch, a datum in its own right when
    comparing kernel variants' compile costs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    zero = jax.device_put(jnp.zeros((8, 128), jnp.uint32))
    t_w = time.perf_counter()
    acc = zero
    for g in groups:  # warm: compile + touch every buffer
        acc = fn(acc, *g)
    np.asarray(acc)
    warm_s = time.perf_counter() - t_w
    t0 = time.perf_counter()
    acc = zero
    for _ in range(passes):
        for g in groups:
            acc = fn(acc, *g)
    np.asarray(acc)
    return time.perf_counter() - t0, warm_s


def _compile_or_shrink(make_fn, host_slabs, k, s, min_s=SLAB_MIN_S):
    """Compile make_fn(s) on slab 0; on failure halve the slab length and
    regenerate buffers. Returns (fn, device_slabs, s)."""
    import jax
    import numpy as np
    while True:
        try:
            fn = make_fn(s)
            dev = [jax.device_put(h) for h in host_slabs]
            jax.block_until_ready(dev)
            y = fn(dev[0])
            np.asarray(y[..., :8])  # real bytes back = compile succeeded
            return fn, dev, s, host_slabs
        except Exception as e:  # noqa: BLE001 — shrink and retry
            if s // 2 < min_s:
                raise
            s //= 2
            log(f"compile failed ({type(e).__name__}); shrinking slab to "
                f"{s / MIB:.0f} MiB/shard")
            n = max(len(host_slabs), -(-GIB // (k * s)))
            host_slabs = _make_slabs(n, k, s)


def child_core() -> None:
    """Smoke + headline encode + rebuild + geometries + CPU baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from seaweedfs_tpu.ops import bitslice, rs_pallas
    from seaweedfs_tpu.ops.rs_jax import Encoder

    res: dict = {}
    dev = jax.devices()[0]
    on_acc = _require_tpu()
    log(f"device: {dev} platform={dev.platform}")

    k, m = 10, 4
    enc = Encoder(k, m)
    coefs = enc.parity_coefs
    seg = rs_pallas.SEG_BYTES

    gf_apply = rs_pallas.apply_gf_matrix if on_acc else \
        bitslice.apply_gf_matrix

    def make_encode(s):
        del s
        return jax.jit(lambda x: gf_apply(coefs, x))

    # -- real-device correctness smoke (gates the headline) ---------------
    t_smoke0 = time.perf_counter()
    _smoke(enc, gf_apply, seg)
    res["smoke_ok"] = True
    log(f"device smoke (encode + 2-shard reconstruct vs oracle): OK "
        f"({time.perf_counter() - t_smoke0:.1f}s)")
    _persist(res)

    # -- headline: ~1 GiB streamed through (1, 10, slab) device calls -----
    s = SLAB_S0 // seg * seg
    if not on_acc:
        s = 2 * MIB  # CPU smoke scale; headline comes from native below
    # 8 slabs exactly on the accelerator: ~1.09 GiB of distinct inputs
    # streams the ~1 GiB workload AND makes one full nargs=8 group (7
    # slabs left the n8 race arms permanently empty).
    n_bufs = 2 if not on_acc else 8
    host_slabs = _make_slabs(n_bufs, k, s)
    encode_fn, dev_slabs, s, host_slabs = _compile_or_shrink(
        make_encode, host_slabs, k, s)
    n_bufs = len(dev_slabs)
    per_call = k * s
    res["slab_s_mib"] = s / MIB
    log(f"slab: (1, {k}, {s}) = {per_call / MIB:.0f} MiB input/call, "
        f"{n_bufs} distinct buffers")

    # Candidate race over (kernel, slabs-per-dispatch, input FORM), all
    # sharing the already-uploaded device slabs. The u8 entries carry
    # XLA copy/reshape/broadcast glue that materializes the tiled u32
    # view of the u8 array, so WORD-FORM candidates feed pre-tiled
    # (B, k, [32,] R, 128) u32 arrays (one-time untimed on-device
    # conversion) and nothing relayouts in the timed path.
    # Every improvement is persisted the moment it lands.
    passes = 3 if on_acc else 1

    def _swar64(c, x):
        return rs_pallas.apply_gf_matrix_swar(c, x, rows_per_block=64)

    def _swarW64(c, x):
        return rs_pallas.apply_gf_matrix_swar_words(c, x,
                                                    rows_per_block=64)

    def _transpW(c, x):
        return rs_pallas.apply_gf_matrix_words(c, x)

    # One-time, untimed conversion of every slab to the word forms the
    # word candidates consume (HBM: u8 + 4-D + 5-D ~= 3x slab bytes).
    w = s // 4
    r4, r5 = w // 128, w // (32 * 128)
    slab_forms = {"u8": dev_slabs}
    if on_acc and r5 > 0:
        import jax.numpy as _jnp

        def _to_w4(x):
            xw = jax.lax.bitcast_convert_type(
                x.reshape(1, k, w, 4), _jnp.uint32)
            return xw.reshape(1, k, r4, 128)

        def _to_w5(x):
            xw = jax.lax.bitcast_convert_type(
                x.reshape(1, k, w, 4), _jnp.uint32)
            return xw.reshape(1, k, 32, r5, 128)

        try:
            f4, f5 = jax.jit(_to_w4), jax.jit(_to_w5)
            slab_forms["w4"] = [f4(d) for d in dev_slabs]
            slab_forms["w5"] = [f5(d) for d in dev_slabs]
            jax.block_until_ready(
                [slab_forms["w4"], slab_forms["w5"]])
        except Exception as e:  # noqa: BLE001 — u8 candidates remain
            log(f"word-form conversion failed: {e}")
            slab_forms.pop("w4", None)
            slab_forms.pop("w5", None)

    def _gate_swar():
        """On-device SWAR-vs-transpose equality, using the SMALL-block
        variant (cheap compile)."""
        try:
            y_t = encode_fn(dev_slabs[0])
            y_s = jax.jit(lambda x: _swar64(coefs, x))(dev_slabs[0])
            eq = bool(np.asarray(jax.jit(
                lambda a, b: (a == b).all())(y_t, y_s)))
            if not eq:
                raise AssertionError("SWAR parity != transpose-kernel parity")
            res["swar_equal_ok"] = True
            log("SWAR kernel on-device equality vs transpose kernel: OK")
            return True
        except Exception as e:  # noqa: BLE001 — SWAR stays out of the race
            res["swar_equal_error"] = f"{type(e).__name__}: {e}"[:200]
            log(f"SWAR equality gate failed; racing transpose only: {e}")
            return False

    # The race list is staged: the u8 transpose candidate runs and
    # persists a headline BEFORE the SWAR gate or any new-form compile
    # is attempted.
    if not on_acc:
        candidates = []  # CPU headline comes from the native codec below
    else:
        # nargs=8 = 1.25 GiB per dispatch (8 x 160 MiB args).
        candidates = [("transpose", gf_apply, 4, "u8"),
                      # production-dispatch smoke: its frac is
                      # recomputed against this run's race afterwards
                      ("dispatch", None, 0, ""),
                      ("gate", None, 0, ""),
                      ("transpW", _transpW, 4, "w5"),
                      ("swarW64", _swarW64, 4, "w4"),
                      ("transpW", _transpW, 8, "w5"),
                      ("swarW64", _swarW64, 8, "w4"),
                      # n16/n32 reuse each uploaded slab 2x/4x per
                      # call; the in-jit fold still forces every encode
                      # to execute
                      ("transpW", _transpW, 16, "w5"),
                      ("swarW64", _swarW64, 16, "w4"),
                      ("transpW", _transpW, 32, "w5")]

    def _race_reference():
        """Best raced transpW number of this run so far."""
        vals = [v for kk, v in res.items()
                if kk.startswith("headline_transpW_")
                and kk.endswith("_gibps")
                and isinstance(v, (int, float))]
        return max(vals, default=None)

    def _dispatch_smoke():
        """The bytes users get from
        Encoder.encode_parity_host (host u8 slab -> zero-copy word view
        -> upload -> words kernel -> _HostParity re-view) must match
        the oracle-smoked kernel, and its cached executable (plus the
        grouped apply_matrix_host_multi one) must run at race speed —
        proving the auto dispatch ships the raced number, not a
        glue-laden cousin."""
        if not (on_acc and "w5" in slab_forms):
            return
        try:
            from seaweedfs_tpu.ops import rs_jax as rs_jax_mod
            old_policy = rs_jax_mod.HOST_DISPATCH
            rs_jax_mod.HOST_DISPATCH = "device"  # smoke the device leg
            try:
                hp = enc.encode_parity_host(host_slabs[0])
                if not isinstance(hp, rs_jax_mod._HostParity):
                    raise AssertionError(
                        "production dispatch did not take the word-form "
                        "device path")
                got = np.asarray(hp)
                want = np.asarray(encode_fn(dev_slabs[0]))
                if not np.array_equal(got, want):
                    raise AssertionError(
                        "production-path parity != oracle-smoked kernel")
            finally:
                rs_jax_mod.HOST_DISPATCH = old_policy
            # time the exact executable the production dispatch cached
            fnp = rs_jax_mod._jitted_apply(
                coefs.tobytes(), m, k, "pallas_words")
            w5 = slab_forms["w5"]
            for d in w5:
                fnp(d)  # warm
            y = None
            t0 = time.perf_counter()
            for _ in range(passes):
                for d in w5:
                    y = fnp(d)
            # single device stream: fetching the LAST output's bytes
            # means every queued kernel before it has run (slice ON
            # DEVICE first — np.asarray(y) whole would drag 160 MiB
            # over the link and poison the timing)
            np.asarray(y[..., :1])
            t_d = time.perf_counter() - t0
            d_gibps = passes * len(w5) * per_call / GIB / t_d
            res["dispatch_device_gibps"] = round(d_gibps, 3)
            race_ref = _race_reference()
            if race_ref:
                res["dispatch_vs_race_frac"] = round(d_gibps / race_ref, 3)
            res["dispatch_path_ok"] = True
            log(f"production dispatch (encode_parity_host words path): "
                f"bytes OK, executable {d_gibps:.2f} GiB/s"
                + (f" ({100 * res['dispatch_vs_race_frac']:.0f}% of "
                   f"raced transpW)" if race_ref else ""))
            _persist(res)
            # grouped production dispatch (apply_matrix_host_multi's
            # executable): n slab args per call, the production analog
            # of the raced transpW_n16 candidate. Reuses each uploaded
            # slab twice per call exactly like the race did.
            ng = min(16, 2 * len(w5))
            fnm = rs_jax_mod._jitted_apply_multi(
                coefs.tobytes(), m, k, "pallas_words", ng)
            grp = tuple(w5[i % len(w5)] for i in range(ng))
            ys = fnm(*grp)  # warm (compile)
            # bytes check: grouped outputs == the single-dispatch
            # executable's outputs for the same slabs (slice on device;
            # fetching whole parities would drag MiBs through the link)
            for j in (0, ng - 1):
                want_j = fnp(grp[j])
                if not np.array_equal(np.asarray(ys[j][..., :1]),
                                      np.asarray(want_j[..., :1])):
                    raise AssertionError(
                        f"grouped dispatch output {j} != single path")
            t0 = time.perf_counter()
            y = None
            for _ in range(passes):
                y = fnm(*grp)
            np.asarray(y[-1][..., :1])
            t_m = time.perf_counter() - t0
            m_gibps = passes * ng * per_call / GIB / t_m
            res["dispatch_multi_gibps"] = round(m_gibps, 3)
            res["dispatch_multi_nargs"] = ng
            if race_ref:
                res["dispatch_multi_vs_race_frac"] = round(
                    m_gibps / race_ref, 3)
            log(f"grouped production dispatch (n={ng}): "
                f"{m_gibps:.2f} GiB/s"
                + (f" ({100 * res['dispatch_multi_vs_race_frac']:.0f}% "
                   f"of raced transpW)" if race_ref else ""))
        except Exception as e:  # noqa: BLE001 — smoke must not kill core
            res["dispatch_path_ok"] = False
            res["dispatch_path_error"] = f"{type(e).__name__}: {e}"[:200]
            log(f"production-dispatch smoke failed: {e}")
        _persist(res)

    compute_gibps = 0.0
    best_name = None
    best_cand = None  # (gf, form, fold) of the winner, set at win time
    swar_ok = False
    # Folded checksum of group 0, per nargs, from a TRUSTED transpose
    # kernel (u8 form is oracle-smoked; all forms hold the same logical
    # bytes in the same flattened order, so their folds agree): SWAR
    # candidates must reproduce it bit-for-bit before their result can
    # count. Reuses each candidate's own (already-warm) timing fn — no
    # extra compiles of the hang-prone variants.
    ref_ck: dict[int, bytes] = {}
    for name, gf, nargs, form in candidates:
        if name == "dispatch":
            _dispatch_smoke()
            _persist(res)
            continue
        if name == "gate":
            swar_ok = _gate_swar()
            _persist(res)
            continue
        if name.startswith("swar") and not swar_ok:
            continue
        slabs = slab_forms.get(form)
        if slabs is None:
            continue  # form conversion failed earlier
        tag = f"headline_{name}_n{nargs}_gibps"
        try:
            fold = _fold_checksum if form == "u8" else _fold_checksum_u32
            fn = _make_folded_fn(gf, coefs, nargs, fold=fold)
            if nargs <= len(slabs):
                groups = [tuple(slabs[i:i + nargs])
                          for i in range(0, n_bufs - nargs + 1, nargs)]
            else:  # wider than the upload pool: wrap (slabs repeat
                # within a call; the fold still runs every encode)
                groups = [tuple(slabs[j % len(slabs)]
                                for j in range(nargs))]
            if not groups:
                raise ValueError(f"need >= {nargs} slabs, have {n_bufs}")
            t, warm_s = _time_folded(fn, groups, passes)
            res[tag.replace("_gibps", "_warm_s")] = round(warm_s, 1)
            import jax.numpy as _jnp
            ck = np.asarray(fn(jax.device_put(
                _jnp.zeros((8, 128), _jnp.uint32)), *groups[0])).tobytes()
            if nargs in ref_ck:
                if ck != ref_ck[nargs]:
                    raise AssertionError(
                        f"{name} checksum diverges from reference kernel")
            elif name.startswith("transp"):
                # first transp* at this nargs becomes the reference; the
                # u8 transpose (oracle-smoked) anchors n4, and transpW
                # is itself checksum-chained to it via ref_ck[4]
                ref_ck[nargs] = ck
            else:
                raise AssertionError(
                    f"no reference checksum for n{nargs}; {name} result "
                    f"cannot be validated")
            n_calls = passes * len(groups)
            nbytes = n_calls * nargs * per_call
            gibps = nbytes / GIB / t
            res[tag] = round(gibps, 3)
            log(f"  {name} x{nargs}/dispatch: {n_calls} calls x "
                f"{nargs * per_call / MIB:.0f} MiB in {t * 1e3:.1f} ms -> "
                f"{gibps:.2f} GiB/s")
            if gibps > compute_gibps:
                compute_gibps = gibps
                best_name = f"{name}_n{nargs}"
                best_cand = (gf, form, fold)
                res["device_compute_gibps"] = round(compute_gibps, 3)
                res["device_compute_bytes"] = nbytes
                res["device_compute_best"] = best_name
                if on_acc:
                    # Persist the headline the moment it exists: a later
                    # sub-bench failing (or the watchdog firing) must
                    # not discard it.
                    res["headline_gibps"] = round(compute_gibps, 3)
        except Exception as e:  # noqa: BLE001 — race survivors decide
            res[tag] = None
            log(f"  {name} x{nargs}/dispatch failed: "
                f"{type(e).__name__}: {e}")
        _persist(res)
    if not candidates:  # degraded CPU path: single folded-call number
        fn = _make_folded_fn(gf_apply, coefs, 1)
        t, _ = _time_folded(fn, [(d,) for d in dev_slabs], passes)
        compute_gibps = passes * n_bufs * per_call / GIB / t
        res["device_compute_gibps"] = round(compute_gibps, 3)
        res["device_compute_bytes"] = passes * n_bufs * per_call
        _persist(res)
    elif best_name is None:
        # Every racer failed: die nonzero, so the parent ends the run
        # instead of reporting an empty "success".
        raise RuntimeError("all headline candidates failed")
    log(f"device-resident encode best ({best_name or 'cpu-fold'}): "
        f"{compute_gibps:.2f} GiB/s (target {TARGET_GIBPS})")

    # -- HBM roofline honesty figure ---------------------------------------
    # v5e HBM is 819 GB/s; an RS(k,m) encode must move at least
    # (read k + write m)/k = (k+m)/k bytes of HBM traffic per input
    # byte, so the physics bound on *input* throughput is HBM/(1+m/k).
    # roofline_frac says how far the measured number is from physics,
    # independent of the 20 GiB/s target constant.
    if on_acc:
        hbm_gibps = 819e9 / GIB
        roofline = hbm_gibps / ((k + m) / k)
        res["hbm_roofline_gibps"] = round(roofline, 1)
        res["roofline_frac"] = round(compute_gibps / roofline, 5)
        log(f"HBM roofline (v5e 819 GB/s, {(k + m) / k:.1f}x traffic): "
            f"{roofline:.0f} GiB/s input bound -> measured is "
            f"{100 * res['roofline_frac']:.2f}% of physics")
        _persist(res)

    # -- production-dispatch frac refresh: the smoke ran EARLY (as a
    # race pseudo-candidate); now that this run's race is in, recompute
    # the fracs against its best candidate.
    rr = _race_reference()
    for key, frac_key in (("dispatch_device_gibps",
                           "dispatch_vs_race_frac"),
                          ("dispatch_multi_gibps",
                           "dispatch_multi_vs_race_frac")):
        v = res.get(key)
        if v and rr:
            res[frac_key] = round(v / rr, 3)
    _persist(res)

    # optional profiler trace of one pass of the plain encode (never fatal)
    try:
        trace_dir = os.path.join(ARTIFACTS, "jax_trace")
        timer = _ChecksumTimer()
        with jax.profiler.trace(trace_dir):
            timer.start()
            for d in dev_slabs[:2]:
                timer.fold(encode_fn(d))
            timer.stop()
        res["profiler_trace"] = trace_dir
        log(f"profiler trace captured: {trace_dir}")
    except Exception as e:  # noqa: BLE001
        log(f"profiler trace unavailable: {e}")

    # -- end-to-end host->device->host stream (the link-bound number) -----
    from seaweedfs_tpu.pipeline import pipe

    e2e_passes = 2 if on_acc else 1

    def batches():
        for _ in range(e2e_passes):
            for h in host_slabs:
                yield None, h

    out_bytes = [0]

    def write(meta, batch, result_np):
        out_bytes[0] += result_np.size

    e2e_stats = pipe.PipeStats()
    # flight recorder + a concurrent profiler burst over the stream:
    # the recorder yields the per-batch occupancy breakdown, the burst
    # captures which HOST code is hot while the stream runs (collapsed
    # stacks under artifacts/ — the flamegraph companion to the trace)
    import threading
    from seaweedfs_tpu.pipeline import flight as flight_mod
    from seaweedfs_tpu.util import profiler as profiler_mod
    flight_mod.arm()
    flight_mod.reset()
    burst_out: list = []

    def _burst():
        try:
            burst_out.append(profiler_mod.profile(seconds=8.0, hz=97))
        except Exception as e:  # noqa: BLE001 — observability only
            burst_out.append(f"# burst failed: {e}")

    burst_t = threading.Thread(target=_burst, name="bench-burst",
                               daemon=True)
    burst_t.start()
    t0 = time.perf_counter()
    n_batches = pipe.run_pipeline(
        batches(), lambda b: encode_fn(jnp.asarray(b)), write,
        stats=e2e_stats, kind="bench.e2e_stream")
    t_e2e = time.perf_counter() - t0
    e2e_bytes = n_batches * per_call
    e2e_gibps = e2e_bytes / GIB / t_e2e
    res["e2e_stream_gibps"] = round(e2e_gibps, 3)
    # per-stage thread-seconds so a regression localizes to a stage
    # (read = batch materialization, compute = dispatch + D2H sync,
    # write = writer-stage work) instead of hiding in one GiB/s number
    res["e2e_stream_stages"] = e2e_stats.stage_seconds()
    try:
        ana = flight_mod.analyze()
        occ = ana.get("occupancy") or {}
        if occ.get("batches"):
            # recorder-derived occupancy re-banks the stage breakdown
            # as busy FRACTIONS of the recorded wall window, and the
            # 0.006 GiB/s figure decomposes into named waits
            res["e2e_stream_occupancy"] = occ["busy_fraction"]
            res["e2e_stream_bottleneck"] = ana["bottleneck"]
            log(f"flight occupancy: {occ['busy_fraction']} -> "
                f"bottleneck {ana['bottleneck']}")
        trace_path = os.path.join(ARTIFACTS,
                                  "e2e_stream_trace_r05.json")
        flight_mod.dump_trace(trace_path)
        res["e2e_stream_trace"] = trace_path
    except Exception as e:  # noqa: BLE001 — observability only
        log(f"flight analysis unavailable: {e}")
    finally:
        flight_mod.disarm()
    burst_t.join(timeout=12.0)
    if burst_out and burst_out[0] and not burst_out[0].startswith("#"):
        stacks_path = os.path.join(ARTIFACTS,
                                   "e2e_stream_stacks_r05.txt")
        with open(stacks_path, "w") as f:
            f.write(burst_out[0])
        res["e2e_stream_stacks"] = stacks_path
        log(f"profiler burst: collapsed stacks -> {stacks_path}")
    log(f"end-to-end h2d->encode->d2h stream: {e2e_bytes / GIB:.2f} GiB in "
        f"{t_e2e:.2f} s -> {e2e_gibps:.2f} GiB/s "
        f"({out_bytes[0] / MIB:.0f} MiB parity returned); stages "
        f"read={e2e_stats.read_seconds:.2f}s "
        f"compute={e2e_stats.compute_seconds:.2f}s "
        f"write={e2e_stats.write_seconds:.2f}s")
    _persist(res)

    # Fastest equality-gated kernel + input form from the race drives
    # the remaining device stages (falling back to the smoked u8
    # transpose path when nothing won).
    if best_cand is not None:
        best_gf, best_form, best_fold = best_cand
    else:
        best_gf, best_form, best_fold = gf_apply, "u8", _fold_checksum

    # -- single-shard rebuild (config 2) ----------------------------------
    present = list(range(14))
    present.remove(13)
    rebuild_coefs = enc.decode_matrix_rows(present, [13])
    rebuild_fn = _make_folded_fn(best_gf, rebuild_coefs, 1,
                                 fold=best_fold)
    t_r, _ = _time_folded(
        rebuild_fn, [(d,) for d in slab_forms[best_form]], passes)
    rebuild_gibps = passes * n_bufs * per_call / GIB / t_r
    res["rebuild_1shard_gibps"] = round(rebuild_gibps, 3)
    log(f"single-shard rebuild: {rebuild_gibps:.2f} GiB/s (target 15)")
    _persist(res)

    # -- alternate geometries (config 4) ----------------------------------
    for (ak, am) in ((6, 3), (12, 4)):
        try:
            aenc = Encoder(ak, am)
            # Keep per-call input within the k=10 slab's verified
            # compile envelope (k*s bytes), whatever ak is — but never
            # below one granule. Granule 2*seg (256 KiB) satisfies every
            # racer: transpose (128 KiB), swar64 (32 KiB), swar512
            # (256 KiB).
            gran = 2 * seg
            a_s = max(gran, min(s, (k * s // ak) // gran * gran))
            a_host = _make_slabs(2, ak, a_s, seed=ak)
            if best_form in ("w4", "w5"):
                a_host = [_host_words(h, best_form) for h in a_host]
            a_dev = [jax.device_put(h) for h in a_host]
            alt_fn = _make_folded_fn(best_gf, aenc.parity_coefs, 1,
                                     fold=best_fold)
            t_a, _ = _time_folded(alt_fn, [(d,) for d in a_dev], passes)
            alt_gibps = passes * len(a_dev) * ak * a_s / GIB / t_a
            res[f"rs_{ak}_{am}_encode_gibps"] = round(alt_gibps, 3)
            log(f"RS({ak},{am}) encode: {alt_gibps:.2f} GiB/s")
        except Exception as e:  # noqa: BLE001 — secondary metric only
            log(f"RS({ak},{am}) bench unavailable: {e}")
    _persist(res)

    # -- end-to-end: synthetic .dat file -> 14 shard files (config 1) -----
    try:
        # The file path writes ~1.4x its input, so the filesystem's raw
        # bandwidth is its ceiling — report the DISK figure under its
        # historical key (cross-round series stays comparable) and the
        # e2e's actual filesystem under its own keys, so storage speed
        # is never misread as codec slowness (PERF.md).
        res["disk_write_gibps"] = round(_disk_write_gibps(), 3)
        # Host-DRAM honesty figure: the e2e file path touches every
        # byte several times on the HOST (memmap read, stripe copy,
        # codec read+write, shard write), so its ceiling is the
        # machine's large-working-set memory bandwidth — NOT the codec.
        # Measured with one cold 256 MiB copy; on this build container
        # a single throttled vCPU moves ~0.17 GiB/s at that size (13 MiB
        # cache-resident loops run ~15x faster, which is why small-probe
        # figures like the GFNI baseline look faster than any e2e can
        # be). Compare encode_e2e_file_gibps against THIS, not against
        # the device or codec numbers.
        res["host_dram_copy_gibps"] = round(_host_dram_copy_gibps(), 3)
        log(f"host DRAM (cold 256 MiB copy): "
            f"{res['host_dram_copy_gibps']:.2f} GiB/s "
            f"(the e2e file path's host-side ceiling)")
        e2e_size = GIB if on_acc else 64 * MIB
        fast = _fast_tmpdir(need_bytes=int(2.6 * e2e_size) + 64 * MIB)
        res["e2e_file_fs"] = "tmpfs" if fast else "disk"
        res["e2e_fs_write_gibps"] = round(
            _disk_write_gibps(directory=fast), 3) if fast \
            else res["disk_write_gibps"]
        log(f"raw disk write: {res['disk_write_gibps']:.2f} GiB/s; "
            f"e2e runs on {res['e2e_file_fs']} "
            f"({res['e2e_fs_write_gibps']:.2f} GiB/s)")
        e2e_file, e2e_file_stages = _bench_end_to_end(
            on_acc, fast)
        res["encode_e2e_file_gibps"] = round(e2e_file, 3)
        if e2e_file_stages:
            res["e2e_file_stages"] = e2e_file_stages
        _persist(res)
    except Exception as e:  # noqa: BLE001 — sub-benches never kill the run
        log(f"end-to-end file bench unavailable: {e}")

    # -- reference-class CPU baseline: native AVX2 codec ------------------
    # The reference's hot loop is klauspost's SIMD Galois assembly; our
    # native/gf256_rs.cpp implements the same nibble-LUT kernel, so its
    # measured rate is this host's AVX2-class baseline for the north
    # star's ">= 10x CPU" clause (BASELINE.md last row).
    cpu_gibps = None
    try:
        from seaweedfs_tpu.ops import rs_native
        cx = np.random.default_rng(0).integers(
            0, 256, (k, 16 * MIB), dtype=np.uint8)
        # steady-state like the reference: klauspost writes into
        # caller-provided shard slices, so the timed loop reuses one
        # output buffer (a fresh 64 MB np.empty per call is page-fault
        # time, not codec time)
        cout = rs_native.apply_gf_matrix(coefs, cx)  # warm (.so, tables)
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            rs_native.apply_gf_matrix(coefs, cx, out=cout)
            best = min(best, time.perf_counter() - t0)
        cpu_gibps = cx.size / GIB / best
        res["cpu_avx2_baseline_gibps"] = round(cpu_gibps, 3)
        log(f"native CPU baseline: {cpu_gibps:.2f} GiB/s "
            f"(simd level {rs_native.simd_level()}; 3=GFNI+AVX512)")
    except Exception as e:  # baseline is informative, never fatal
        log(f"native CPU baseline unavailable: {e}")

    # Headline: the device-resident number on an accelerator. When this
    # child runs on CPU (degraded), the honest headline is the DISPATCHED
    # CPU path — the native AVX2 codec — with the XLA-network number kept
    # in extras (round-2 advisor finding).
    if on_acc:
        headline = compute_gibps
    else:
        res["cpu_xla_bitslice_gibps"] = round(compute_gibps, 3)
        headline = cpu_gibps if cpu_gibps is not None else compute_gibps
    res["headline_gibps"] = round(headline, 3)
    if cpu_gibps:
        res["speedup_vs_cpu"] = round(headline / cpu_gibps, 2)
    _persist(res)
    print(json.dumps(res), flush=True)


def _smoke(enc, gf_apply, seg: int) -> None:
    """Encode + 2-shard reconstruct of one slab on the REAL backend,
    checked byte-for-byte against the NumPy oracle. Raises on mismatch."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ops import rs_ref

    k, m = enc.data_shards, enc.parity_shards
    rng = np.random.default_rng(42)
    x = rng.integers(0, 256, (1, k, seg), dtype=np.uint8)
    ref = rs_ref.ReferenceEncoder(k, m)
    shards = [x[0, i].copy() for i in range(k)] + \
             [np.zeros(seg, dtype=np.uint8) for _ in range(m)]
    ref.encode(shards)
    want_parity = np.stack(shards[k:])

    fn = jax.jit(lambda v: gf_apply(enc.parity_coefs, v))
    got = np.asarray(fn(jax.device_put(x)))[0]
    if not np.array_equal(got, want_parity):
        raise AssertionError("device encode mismatch vs NumPy oracle")

    # lose shards 0 (data) and 11 (parity); rebuild from survivors
    present = [i for i in range(k + m) if i not in (0, 11)]
    rows = enc.decode_matrix_rows(present, [0, 11])
    # decode rows are expressed over the FIRST k survivors
    surv = np.stack([shards[i] for i in present[:k]])[None]
    fn2 = jax.jit(lambda v: gf_apply(rows, v))
    got2 = np.asarray(fn2(jax.device_put(surv)))[0]
    if not np.array_equal(got2[0], shards[0]):
        raise AssertionError("device data-shard reconstruct mismatch")
    if not np.array_equal(got2[1], shards[11]):
        raise AssertionError("device parity-shard reconstruct mismatch")


def _disk_write_gibps(n_bytes: int = 64 * MIB,
                      directory: str | None = None) -> float:
    """Raw sequential write bandwidth of a filesystem."""
    import tempfile

    import numpy as np

    buf = np.random.default_rng(1).integers(0, 256, n_bytes,
                                            dtype=np.uint8)
    with tempfile.NamedTemporaryFile(dir=directory) as f:
        t0 = time.perf_counter()
        buf.tofile(f)
        f.flush()
        os.fsync(f.fileno())
        dt = time.perf_counter() - t0
    return n_bytes / GIB / dt


def _host_dram_copy_gibps(n_bytes: int = 256 * MIB) -> float:
    """Large-working-set host memory bandwidth: one cold copy of a
    fresh buffer (too big for cache, so both the read and the write
    stream hit DRAM). This is the host-side ceiling for any e2e file
    path — see the honesty note at the call site."""
    import numpy as np

    src = np.random.default_rng(3).integers(0, 256, n_bytes,
                                            dtype=np.uint8)
    t0 = time.perf_counter()
    dst = src.copy()
    dt = time.perf_counter() - t0
    del dst
    return n_bytes / GIB / dt


def _fast_tmpdir(need_bytes: int) -> str | None:
    """/dev/shm when usable AND large enough — the container disk
    writes ~0.1 GiB/s, which would measure the disk, not the encode
    pipeline (PERF.md: tmpfs measured ~2.6 GiB/s on this host). A
    64 MiB default-shm container must fall back to disk, not ENOSPC
    away the whole e2e metric."""
    shm = "/dev/shm"
    try:
        import tempfile
        with tempfile.NamedTemporaryFile(dir=shm):
            pass
        st = os.statvfs(shm)
        if st.f_bavail * st.f_frsize < need_bytes:
            return None
        return shm
    except OSError:
        return None


def _bench_end_to_end(on_acc: bool, fast: str | None):
    """Config 1 end-to-end: synthetic .dat -> 14 shard files, through
    the pipelined encode path (IO / H2D / compute / D2H overlap).
    Returns (GiB/s of .dat bytes processed, per-stage seconds dict from
    the pipeline's own accounting). ``fast`` is the tmpfs dir
    child_core already probed (None = default disk) — passed in so the
    recorded e2e_file_fs always names the filesystem actually used."""
    import tempfile

    import numpy as np

    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.storage import superblock as superblock_mod
    from seaweedfs_tpu.storage import volume as volume_mod

    size = GIB if on_acc else 64 * MIB
    if fast is None:
        size = min(size, 256 * MIB)  # don't grind the slow disk for 1 GiB
    # Warm the one-time costs OUT of the timed window (the bench's own
    # honesty rule #3 — warm-up never counts): the hybrid dispatch's
    # first encode triggers the native codec's g++ build + table setup
    # and the link-vs-codec calibration probes, which would otherwise
    # land inside the e2e clock. The throwaway encode is sized to reproduce the
    # main run's steady-state batch shape (grouped cap // row bytes
    # rows, plus one tail row), so the device leg's width-1 executable
    # compiles pre-clock too. Residual honesty note: on a fast-link
    # accelerator the grouped multi-width executables may still
    # first-compile in-window — the warm volume can't enumerate them.
    try:
        from seaweedfs_tpu.ops import rs_jax as rs_jax_mod
        from seaweedfs_tpu.ops import rs_native as rs_native_mod
        from seaweedfs_tpu.pipeline import pipe as pipe_mod
        from seaweedfs_tpu.pipeline.scheme import DEFAULT_SCHEME
        if rs_native_mod.available():
            rs_native_mod.apply_gf_matrix(
                np.ones((4, 10), dtype=np.uint8),
                np.zeros((10, 1 << 16), dtype=np.uint8))
        rs_jax_mod._device_worth_it()
        row = DEFAULT_SCHEME.data_shards * DEFAULT_SCHEME.small_block_size
        rpb = max(1, pipe_mod.current().grouped_batch_bytes // row)
        warm_bytes = min((rpb + 1) * row + 8, size)
        with tempfile.TemporaryDirectory(dir=fast) as wtd:
            wbase = os.path.join(wtd, "0")
            with open(volume_mod.dat_path(wbase), "wb") as f:
                f.write(superblock_mod.SuperBlock().to_bytes())
                f.write(np.zeros(warm_bytes - 8, dtype=np.uint8)
                        .tobytes())
            encode_mod.write_ec_files(wbase)
    except Exception as e:  # noqa: BLE001 — warm-up must never kill e2e
        log(f"e2e warm-up skipped: {e}")
    with tempfile.TemporaryDirectory(dir=fast) as td:
        base = os.path.join(td, "1")
        rng = np.random.default_rng(7)
        with open(volume_mod.dat_path(base), "wb") as f:
            f.write(superblock_mod.SuperBlock().to_bytes())
            remaining = size - 8
            chunk = 64 * MIB
            while remaining > 0:
                n = min(chunk, remaining)
                f.write(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
                remaining -= n
        from seaweedfs_tpu.pipeline import pipe as pipe_stats_mod
        file_stats = pipe_stats_mod.PipeStats()
        t0 = time.perf_counter()
        encode_mod.write_ec_files(base, stats=file_stats)
        dt = time.perf_counter() - t0
        gibps = size / GIB / dt
        stages = file_stats.stage_seconds()
        log(f"end-to-end file encode ({size / GIB:.2f} GiB .dat): "
            f"{dt:.2f} s -> {gibps:.2f} GiB/s; stages "
            f"read={stages['read']}s compute={stages['compute']}s "
            f"write={stages['write']}s")
        return gibps, stages


def child_config3() -> None:
    """Config 3: many small volumes coalesced into large device batches.

    Payloads are drawn from a small pool of distinct buffers instead of
    materializing N full volumes (1000 x 30 MB would be ~30 GB of host
    RAM — round-2 advisor finding); the batcher only reads them.

    On the accelerator TWO numbers are reported (the full 29.3 GiB
    workload end to end does not fit the stage's watchdog):

    * ``many_volumes_gibps`` — device-resident aggregate over the EXACT
      coalesced batch shapes the 1000-volume workload generates
      (measured per-shape batch census on a volume subset, scaled),
      timed with the in-jit folded checksum. This is the chip's honest
      aggregate rate for the workload's launch pattern.
    * ``many_volumes_e2e_gibps`` — the full host->device->host batcher
      path on a sampled volume count sized for the watchdog, with the
      sample size reported alongside."""
    import numpy as np

    from seaweedfs_tpu.pipeline import batch as batch_mod, pipe

    on_acc = _require_tpu()
    n_volumes = 1000 if on_acc else 32
    vol_bytes = 30 * MIB if on_acc else MIB
    max_batch = 128 * MIB if on_acc else pipe.current().batch_bytes
    pool_n = 8
    rng = np.random.default_rng(3)
    pool = [rng.integers(0, 256, vol_bytes, dtype=np.uint8)
            for _ in range(pool_n)]
    res: dict = {}

    if not on_acc:
        payloads = [pool[i % pool_n] for i in range(n_volumes)]
        batch_mod.encode_many(payloads[:2], max_batch_bytes=max_batch)
        t0 = time.perf_counter()
        total, _ = batch_mod.encode_many(payloads,
                                         max_batch_bytes=max_batch)
        dt = time.perf_counter() - t0
        gibps = total / GIB / dt
        log(f"config-3 coalesced encode ({n_volumes} x "
            f"{vol_bytes / MIB:.0f} MB): {dt:.2f} s -> "
            f"{gibps:.2f} GiB/s aggregate")
        res["many_volumes_gibps"] = round(gibps, 3)
        _persist(res)
        print(json.dumps(res), flush=True)
        return

    import jax

    from seaweedfs_tpu.pipeline.scheme import DEFAULT_SCHEME

    # -- batch census on a subset, scaled to the full workload ------------
    # Full batches (those that hit the bound's row cap) scale with the
    # volume count; the end-of-stream tail flush happens ONCE however
    # many volumes stream through, so it is counted once, unscaled —
    # scaling it would skew the timed batch mix toward the tail shape.
    census_n = 40
    census_src = ((i, pool[i % pool_n]) for i in range(census_n))
    shapes: dict = {}
    for spans, packed in batch_mod.iter_packed_batches(
            census_src, max_batch_bytes=max_batch):
        rows_cap = batch_mod.max_rows_per_batch(
            packed.shape[1], packed.shape[2], max_batch)
        full = packed.shape[0] >= rows_cap
        key = packed.shape
        ent = shapes.setdefault(key, {"batches": 0, "bytes": 0,
                                      "full": full, "proto": packed})
        ent["batches"] += 1
        ent["bytes"] += packed.size
    scale = n_volumes / census_n
    total_bytes = int(sum(
        e["bytes"] * (scale if e["full"] else 1) for e in shapes.values()))
    log("config-3 batch census (x{:.0f} scale on full batches): ".format(
        scale) + ", ".join(
        f"{v['batches']}x{k}{'' if v['full'] else ' (tail)'}"
        for k, v in shapes.items()))

    # -- device-resident aggregate over those shapes ----------------------
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import bitslice, rs_pallas

    enc = DEFAULT_SCHEME.encoder
    coefs = enc.parity_coefs
    t_total = 0.0
    n_distinct = 4
    for shape, ent in shapes.items():
        n_calls = max(1, round(ent["batches"] * scale)) if ent["full"] \
            else ent["batches"]
        proto = ent["proto"]
        block = proto.shape[-1]
        # Pre-tiled word form when the block conforms (zero-copy host
        # view; no XLA relayout on device), u8 + bitslice otherwise.
        if rs_pallas.conforms(block):
            def _prep(p):
                return _host_words(p, "w5")
            gf = lambda c, x: rs_pallas.apply_gf_matrix_words(c, x)  # noqa: E731
            fold = _fold_checksum_u32
        else:
            def _prep(p):
                return p
            gf = lambda c, x: bitslice.apply_gf_matrix(c, x)  # noqa: E731
            fold = _fold_checksum
        # distinct buffers via cheap byte-XOR (a permutation would cost
        # minutes of host time at these sizes)
        bufs = [jax.device_put(_prep(proto ^ np.uint8(17 * i + 1)))
                for i in range(min(n_distinct, n_calls))]
        fn = _make_folded_fn(gf, coefs, 1, fold=fold)
        zero = jax.device_put(jnp.zeros((8, 128), jnp.uint32))
        acc = zero
        for b in bufs:  # warm: compile + touch every buffer
            acc = fn(acc, b)
        np.asarray(acc)
        acc = zero
        t0 = time.perf_counter()
        for i in range(n_calls):
            acc = fn(acc, bufs[i % len(bufs)])
        np.asarray(acc)
        t_total += time.perf_counter() - t0
    gibps = total_bytes / GIB / t_total
    res["many_volumes_gibps"] = round(gibps, 3)
    res["many_volumes_batches"] = int(sum(
        round(e["batches"] * scale) if e["full"] else e["batches"]
        for e in shapes.values()))
    log(f"config-3 device-resident aggregate ({n_volumes} x "
        f"{vol_bytes / MIB:.0f} MB as {res['many_volumes_batches']} "
        f"coalesced batches): {t_total:.2f} s -> {gibps:.2f} GiB/s")
    _persist(res)

    # -- sampled end-to-end over the link ---------------------------------
    sample = 24
    payloads = [pool[i % pool_n] for i in range(sample)]
    batch_mod.encode_many(payloads[:2], max_batch_bytes=max_batch)
    t0 = time.perf_counter()
    total, _ = batch_mod.encode_many(payloads, max_batch_bytes=max_batch)
    dt = time.perf_counter() - t0
    e2e = total / GIB / dt
    res["many_volumes_e2e_gibps"] = round(e2e, 3)
    res["many_volumes_e2e_sample"] = sample
    log(f"config-3 e2e sampled ({sample} x {vol_bytes / MIB:.0f} MB "
        f"over the link): {dt:.2f} s -> {e2e:.2f} GiB/s")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_config5() -> None:
    """Config 5: streaming 4-shard-loss decode while 64-QPS concurrent
    interval repairs ride the micro-batch aggregator.

    On the accelerator a device-resident 4-loss reconstruct rate is
    reported alongside the e2e harness numbers. The harness itself now
    rides the HYBRID dispatch policy (rs_jax): sub-slab interval
    repairs always take the host AVX2 codec (a 4 KiB repair must never
    pay a device round trip), and bulk chunks cross to the device only
    when the measured link outruns the host codec."""
    import numpy as np

    from seaweedfs_tpu.pipeline import repair_bench
    from seaweedfs_tpu.pipeline.scheme import DEFAULT_SCHEME

    on_acc = _require_tpu()
    res: dict = {}

    if on_acc:
        # Guarded: a compile failure here must not cost the (previously
        # working) repair harness numbers below.
        try:
            import jax

            from seaweedfs_tpu.ops import rs_pallas

            enc = DEFAULT_SCHEME.encoder
            k, total = enc.data_shards, enc.data_shards + enc.parity_shards
            lost = list(repair_bench.DEFAULT_LOST)
            survivors = [i for i in range(total) if i not in lost]
            rows = enc.decode_matrix_rows(survivors, lost)
            s = 16 * MIB
            # upload in the pre-tiled word form: the host view is
            # zero-copy, and the words kernel runs without XLA relayout
            host = [_host_words(h, "w5")
                    for h in _make_slabs(4, k, s, seed=55)]
            dev = [jax.device_put(h) for h in host]
            fn = _make_folded_fn(
                lambda c, x: rs_pallas.apply_gf_matrix_words(c, x),
                rows, 1, fold=_fold_checksum_u32)
            t, _ = _time_folded(fn, [(d,) for d in dev], passes=3)
            n_bytes = 3 * len(dev) * k * s
            gibps = n_bytes / GIB / t
            res["repair_decode_device_gibps"] = round(gibps, 3)
            log(f"config-5 device-resident 4-loss reconstruct: "
                f"{gibps:.2f} GiB/s")
        except Exception as e:  # noqa: BLE001 — secondary metric only
            log(f"config-5 device-resident reconstruct unavailable: {e}")
        _persist(res)

    shard_len = (8 * MIB) if on_acc else (2 * MIB)
    r = repair_bench.run(
        duration_s=8.0 if on_acc else 3.0,
        qps=64,
        shard_len=shard_len)
    log(f"config-5 repair-under-load: decode {r['decode_gibps']:.2f} "
        f"GiB/s sustained, read p99 {r['read_p99_ms']:.2f} ms")
    res.update({"repair_decode_gibps": round(r["decode_gibps"], 3),
                "repair_read_p99_ms": round(r["read_p99_ms"], 3),
                # shape-dependent numbers: record the workload geometry
                # so cross-round trend comparisons stay apples-to-apples
                "repair_shard_len_mib": shard_len // MIB})
    # Surface which leg the hybrid dispatcher chose (and why): with a
    # degraded link the harness honestly rides the host codec; a local
    # chip crosses to the device word path. The chip's own repair math
    # is repair_decode_device_gibps above either way.
    try:
        from seaweedfs_tpu.ops import rs_jax as rs_jax_mod
        if rs_jax_mod._link_gibps is not None:
            res["dispatch_link_gibps"] = round(rs_jax_mod._link_gibps, 3)
            res["dispatch_native_gibps"] = round(
                rs_jax_mod._native_gibps, 3)
            res["repair_dispatch"] = (
                "device" if rs_jax_mod._link_gibps >
                rs_jax_mod._native_gibps else "hybrid-native")
            log(f"config-5 hybrid dispatch: link "
                f"{res['dispatch_link_gibps']} GiB/s vs native "
                f"{res['dispatch_native_gibps']} GiB/s -> "
                f"{res['repair_dispatch']}")
    except Exception:  # noqa: BLE001 — observability only
        pass
    _persist(res)
    print(json.dumps(res), flush=True)


def child_cache() -> None:
    """Zipfian hot-read benchmark of the chunk cache (docs/cache.md).

    64 x 1 MiB on-disk "chunks" stand in for volume-server needle
    payloads. Three measured passes:

    1. uncached floor — every access is a filesystem open+read;
    2. zipfian read-through — 10% of keys take 90% of the traffic
       through a ChunkCache sized well below the working set; this pass
       owns ``cache_hit_ratio`` (acceptance: >= 0.8) and the effective
       mixed throughput;
    3. hot re-read — the workload's hot head once it is resident, i.e.
       what a hit actually costs; ``cache_hot_read_gibps`` vs the floor
       is the headline speedup (acceptance: >= 5x).

    The mixed pass is reported too (``cache_zipfian_read_gibps``) so
    the miss-bound effective figure is never hidden."""
    import random
    import shutil
    import tempfile

    from seaweedfs_tpu.cache import ChunkCache

    chunk_bytes = MIB        # the mount/filer layers' chunk size scale
    n_chunks = 64
    accesses = 2000
    rng = random.Random(1234)
    tmp = tempfile.mkdtemp(prefix="bench_cache_")
    try:
        paths = []
        for i in range(n_chunks):
            p = os.path.join(tmp, f"chunk_{i:03d}")
            with open(p, "wb") as f:
                f.write(os.urandom(chunk_bytes))
            paths.append(p)

        hot = list(range(max(1, n_chunks // 10)))
        seq = [rng.choice(hot) if rng.random() < 0.9
               else rng.randrange(n_chunks) for _ in range(accesses)]

        def disk_read(i: int) -> bytes:
            with open(paths[i], "rb") as f:
                return f.read()

        # pass 1 — uncached floor: every access pays the filesystem
        t0 = time.perf_counter()
        for i in seq:
            disk_read(i)
        t_uncached = time.perf_counter() - t0

        cache = ChunkCache(12 * chunk_bytes, admission_max_fraction=0.2)

        def read_through(i: int) -> bytes:
            b = cache.get(f"c{i}")
            if b is None:
                b = disk_read(i)
                cache.put(f"c{i}", b)
            return b

        # pass 2 — zipfian read-through (hit ratio + effective number)
        t0 = time.perf_counter()
        for i in seq:
            read_through(i)
        t_mixed = time.perf_counter() - t0
        st = cache.stats()

        # pass 3 — hot head, resident: the cost of a hit
        hot_seq = [rng.choice(hot) for _ in range(accesses)]
        for i in hot:
            read_through(i)   # ensure residency
        t0 = time.perf_counter()
        for i in hot_seq:
            read_through(i)
        t_hot = time.perf_counter() - t0

        total = accesses * chunk_bytes
        res = {
            "cache_hot_read_gibps": round(total / GIB / t_hot, 3),
            "cache_zipfian_read_gibps": round(total / GIB / t_mixed, 3),
            "cache_uncached_read_gibps":
                round(total / GIB / t_uncached, 3),
            "cache_hit_ratio": round(st["hit_ratio"], 4),
            "cache_speedup": round(t_uncached / t_hot, 2),
        }
        cache.close()
        log(f"cache stage: hot {res['cache_hot_read_gibps']} GiB/s, "
            f"zipfian {res['cache_zipfian_read_gibps']} GiB/s, "
            f"uncached {res['cache_uncached_read_gibps']} GiB/s "
            f"(hot speedup {res['cache_speedup']}x, hit ratio "
            f"{res['cache_hit_ratio']})")
        _persist(res)
        print(json.dumps(res), flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


#: Server half of the trace/telemetry overhead stages: master + volume
#: + filer in ONE subprocess, so client-visible latency crosses a real
#: process boundary (co-locating client and servers would bill every
#: server-side GIL hold to the client and overstate the tax).
#: The observability plane named by argv[2] ("tracing" or "telemetry")
#: toggles at runtime via stdin ("on"/"off" lines) so both modes are
#: measured against the SAME process — separate clusters differ by
#: ±20us in baseline latency, swamping the signal.
_OVERHEAD_SERVER_HELPER = r"""
import sys, socket, time
from seaweedfs_tpu.cluster import telemetry
from seaweedfs_tpu.cluster.filer_server import FilerServer
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.filer.filer import Filer
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import tracing

if sys.argv[2] == "tracing":
    plane = tracing
elif sys.argv[2] == "telemetry":
    plane = telemetry
elif sys.argv[2] == "profiler":
    # on = the always-on low-rate sampler thread (1 Hz default); the
    # tax a request pays is GIL time stolen by the frame walk.
    from seaweedfs_tpu.util import profiler as _profiler
    class plane:
        @staticmethod
        def configure(enabled):
            _profiler.configure(enabled=enabled, hz=1.0)
elif sys.argv[2] == "usage":
    # on = every filer request folds a tenant/bucket counter row plus a
    # latency-digest insert and a SpaceSaving offer under the
    # collector's lock, and the volume server offers each needle read
    # into its hot-key sketch; off = the module-level flag fast path.
    from seaweedfs_tpu.cluster import usage as plane
elif sys.argv[2] == "jobs":
    # on = the maintenance plane idling: module switch armed (volume-
    # server claim polls + heartbeat job_progress piggyback) plus the
    # master's replication-policy loop ticking every pulse over live
    # telemetry; nothing is ever submitted, so the difference is
    # exactly the plane's idle tax on an unrelated read path.
    from seaweedfs_tpu.cluster import jobs as _jobs
    class plane:
        @staticmethod
        def configure(enabled):
            _jobs.configure(enabled=enabled)
            master.policy.enabled = enabled
            master.policy.interval = 0.2
elif sys.argv[2] == "ingress":
    # on = the full admission path on every request (per-request
    # counter, deadline-header parse, queue-pressure probe); off = the
    # gate's disabled fast path. The worker pool, bounded queue and
    # keep-alive core are structural and serve both modes identically,
    # so the diff is exactly the per-request admission tax.
    from seaweedfs_tpu.util import httpserver as plane
elif sys.argv[2] == "scrub":
    # on = a background scrub thread CRC-walking both the SAME volume
    # the foreground reads are served from and a large synthetic one,
    # under the production token-bucket pacer (8 MiB/s default) — the
    # docs/robustness.md steady state while a pass is in flight. The
    # big volume keeps the pass spanning whole measurement blocks, the
    # way an hour-long production pass would (without it the tiny
    # served volume re-scrubs ~8x/s and the per-PASS sidecar fsync
    # becomes a per-125ms artifact no real deployment pays). The pacer
    # sleeps outside the volume lock, so the diff is the paced
    # read+CRC foreground tax; off = scrubber idle.
    import threading
    from seaweedfs_tpu.storage import scrubber as _scrubber
    from seaweedfs_tpu.storage.volume import generate_synthetic_volume
    class plane:
        _stop = None
        _thr = None
        _extra = None
        @staticmethod
        def _loop(stop):
            # interruptible pacing + per-needle abort so the off-
            # toggle's join() never waits out a multi-second pass
            class _Abort(Exception):
                pass
            def _prog(frac):
                if stop.is_set():
                    raise _Abort
            pacer = _scrubber.RatePacer(sleep=lambda s: stop.wait(s))
            while not stop.is_set():
                for v in (list(vol.store.volumes.values())
                          + [plane._extra]):
                    if stop.is_set():
                        break
                    try:
                        _scrubber.scrub_volume(v, pacer, progress=_prog)
                    except _Abort:
                        break
                    except Exception:
                        pass
        @staticmethod
        def configure(enabled):
            if enabled and plane._thr is None:
                if plane._extra is None:
                    import os as _os
                    d = _os.path.join(sys.argv[1], "scrub_extra")
                    _os.makedirs(d, exist_ok=True)
                    plane._extra = generate_synthetic_volume(
                        _os.path.join(d, "99"), 99, n_needles=256,
                        avg_size=128 * 1024, seed=3)
                plane._stop = threading.Event()
                plane._thr = threading.Thread(
                    target=plane._loop, args=(plane._stop,),
                    daemon=True)
                plane._thr.start()
            elif not enabled and plane._thr is not None:
                plane._stop.set()
                plane._thr.join()
                plane._thr = None
else:  # "faults": on = armed-but-inert spec, so every fault point in
    # the read path pays the real armed cost (dict lookup miss) while
    # injecting nothing; off = the disarmed single-flag fast path.
    from seaweedfs_tpu.util import faults as _faults
    class plane:
        @staticmethod
        def configure(enabled):
            if enabled:
                _faults.inject("bench.noop", "delay:0@0")
            else:
                _faults.clear()

def fpp():
    for _ in range(50):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65535:
            continue
        try:
            with socket.socket() as s2:
                s2.bind(("127.0.0.1", p + 10000))
            return p
        except OSError:
            continue
    raise RuntimeError("no free port pair")

master = MasterServer(port=fpp(), volume_size_limit_mb=64,
                      pulse_seconds=0.2, seed=7).start()
vol = VolumeServer(Store([sys.argv[1]], max_volumes=8), port=fpp(),
                   master_url=master.url, pulse_seconds=0.2).start()
filer = FilerServer(Filer(), port=fpp(),
                    master_url=master.url).start()
deadline = time.time() + 15
while time.time() < deadline and not master.topology.nodes:
    time.sleep(0.05)
print("READY", filer.url, flush=True)
for line in sys.stdin:
    plane.configure(enabled=(line.strip() == "on"))
    print("ACK", flush=True)
"""


def _measure_plane_overhead(plane: str) -> tuple:
    """Median warm 1 MiB filer-read latency with the named
    observability plane off vs on. Shared harness for the trace- and
    telemetry-overhead stages: one subprocess cluster serves both
    modes (separate clusters differ by more than the instrumentation
    cost in baseline latency) and per-request medians discard
    scheduler stalls. Returns ``(t_off, t_on)`` seconds."""
    import shutil
    import statistics
    import tempfile
    import urllib.request

    tmp = tempfile.mkdtemp(prefix=f"bench_{plane}_")
    proc = subprocess.Popen(
        [sys.executable, "-c", _OVERHEAD_SERVER_HELPER, tmp, plane],
        env=dict(os.environ), cwd=REPO, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    try:
        line = proc.stdout.readline().split()
        if not line or line[0] != "READY":
            raise RuntimeError(f"{plane} helper failed to boot")
        url = f"http://{line[1]}/bench/{plane}.bin"
        req = urllib.request.Request(url, data=os.urandom(MIB),
                                     method="PUT")
        with urllib.request.urlopen(req) as r:
            r.read()

        def set_mode(mode: str) -> None:
            proc.stdin.write(mode + "\n")
            proc.stdin.flush()
            if proc.stdout.readline().strip() != "ACK":
                raise RuntimeError(f"{plane} helper lost")

        def block(count: int) -> list:
            lat = []
            for _ in range(count):
                t0 = time.perf_counter()
                with urllib.request.urlopen(url) as r:
                    r.read()
                lat.append(time.perf_counter() - t0)
            return lat

        block(60)  # warm: chunk cache resident, lookups cached
        lat = {"off": [], "on": []}
        diffs = []
        for rnd in range(24):
            order = ("off", "on") if rnd % 2 == 0 else ("on", "off")
            rmed = {}
            for mode in order:
                set_mode(mode)
                block(30)
                samples = block(150)
                lat[mode] += samples
                rmed[mode] = statistics.median(samples)
            diffs.append(rmed["on"] - rmed["off"])
        # The planes under test cost well under the run-to-run drift of
        # a localhost HTTP read, so estimate the DIFFERENCE from paired
        # adjacent blocks (drift cancels within a round; alternating
        # order cancels within-round drift across rounds) instead of
        # subtracting two noisy grand medians; the interquartile mean
        # of the round diffs sheds lag-spike tails without the
        # inefficiency of a lone median.
        diffs.sort()
        q = len(diffs) // 4
        delta = statistics.fmean(diffs[q:len(diffs) - q])
        t_off = statistics.median(lat["off"])
        return (t_off, t_off + delta)
    finally:
        proc.kill()
        proc.wait()
        shutil.rmtree(tmp, ignore_errors=True)


def child_trace_overhead() -> None:
    """Tracing tax on the cached-read path (docs/observability.md).

    Boots the read stack (master + volume + filer) in a subprocess
    and times warm filer GETs of a chunk-sized (1 MiB, the cache
    stage's chunk scale) object — the cached read this PR's tracing
    instruments end to end — with tracing toggled off/on between
    interleaved blocks via the helper's stdin.
    Acceptance (ISSUE 2): overhead < 5%."""
    t_off, t_on = _measure_plane_overhead("tracing")
    overhead = (t_on - t_off) / t_off
    res = {
        "trace_overhead_pct": round(overhead * 100, 2),
        "trace_read_us_off": round(t_off * 1e6, 1),
        "trace_read_us_on": round(t_on * 1e6, 1),
        "trace_overhead_ok": bool(overhead < 0.05),
    }
    log(f"trace stage: cached read {res['trace_read_us_off']}us "
        f"off / {res['trace_read_us_on']}us on -> "
        f"{res['trace_overhead_pct']}% overhead "
        f"({'OK' if res['trace_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_telemetry_overhead() -> None:
    """Telemetry-collection tax on the same cached-read path.

    Identical harness to the trace stage, but the stdin toggle flips
    ``telemetry.configure(enabled=...)`` on the server process, so the
    difference is exactly the per-request collector cost (counter
    bumps + digest appends) plus the per-pulse snapshot drain.
    Acceptance (ISSUE 4): overhead < 5%."""
    t_off, t_on = _measure_plane_overhead("telemetry")
    overhead = (t_on - t_off) / t_off
    res = {
        "telemetry_overhead_pct": round(overhead * 100, 2),
        "telemetry_read_us_off": round(t_off * 1e6, 1),
        "telemetry_read_us_on": round(t_on * 1e6, 1),
        "telemetry_overhead_ok": bool(overhead < 0.05),
    }
    log(f"telemetry stage: cached read "
        f"{res['telemetry_read_us_off']}us off / "
        f"{res['telemetry_read_us_on']}us on -> "
        f"{res['telemetry_overhead_pct']}% overhead "
        f"({'OK' if res['telemetry_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_fault_overhead() -> None:
    """Fault-injection-plane tax on the cached-read path when NOTHING
    is injected (docs/robustness.md).

    Same harness as the trace/telemetry stages. "off" is the default
    disarmed state (every ``faults.check`` is one module-flag test);
    "on" arms a never-firing spec at an unused point, which is the
    worst armed-but-quiet case: every real fault point in the read
    path now also pays the specs-dict lookup miss.
    Acceptance (ISSUE 5): overhead < 2%."""
    t_off, t_on = _measure_plane_overhead("faults")
    overhead = (t_on - t_off) / t_off
    res = {
        "fault_overhead_pct": round(overhead * 100, 2),
        "fault_read_us_off": round(t_off * 1e6, 1),
        "fault_read_us_on": round(t_on * 1e6, 1),
        "fault_overhead_ok": bool(overhead < 0.02),
    }
    log(f"fault stage: cached read {res['fault_read_us_off']}us "
        f"off / {res['fault_read_us_on']}us on -> "
        f"{res['fault_overhead_pct']}% overhead "
        f"({'OK' if res['fault_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_profile_overhead() -> None:
    """Continuous-profiler tax on the cached-read path
    (docs/observability.md).

    Same paired-block harness as the trace/telemetry/fault stages; the
    stdin toggle flips ``profiler.configure(enabled=...)`` on the
    server process, so the difference is exactly the always-on
    sampler's cost: one ``sys._current_frames()`` walk + collapsed-
    stack fold per second, amortized across the requests in flight.
    Acceptance (ISSUE 7): overhead < 5%."""
    t_off, t_on = _measure_plane_overhead("profiler")
    overhead = (t_on - t_off) / t_off
    res = {
        "profile_overhead_pct": round(overhead * 100, 2),
        "profile_read_us_off": round(t_off * 1e6, 1),
        "profile_read_us_on": round(t_on * 1e6, 1),
        "profile_overhead_ok": bool(overhead < 0.05),
    }
    log(f"profile stage: cached read {res['profile_read_us_off']}us "
        f"off / {res['profile_read_us_on']}us on -> "
        f"{res['profile_overhead_pct']}% overhead "
        f"({'OK' if res['profile_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_usage_overhead() -> None:
    """Per-tenant usage-accounting tax on the cached-read path
    (docs/observability.md "usage accounting & ranked reads").

    Same paired-block harness as the other observability stages; the
    stdin toggle flips ``usage.configure(enabled=...)`` on the server
    process, so the difference is exactly the metering cost: one
    counter-row fold + latency-digest insert + SpaceSaving offer on
    the filer, and one hot-key sketch offer on the volume server, per
    request. Acceptance (ISSUE 8): overhead < 5%."""
    t_off, t_on = _measure_plane_overhead("usage")
    overhead = (t_on - t_off) / t_off
    res = {
        "usage_overhead_pct": round(overhead * 100, 2),
        "usage_read_us_off": round(t_off * 1e6, 1),
        "usage_read_us_on": round(t_on * 1e6, 1),
        "usage_overhead_ok": bool(overhead < 0.05),
    }
    log(f"usage stage: cached read {res['usage_read_us_off']}us "
        f"off / {res['usage_read_us_on']}us on -> "
        f"{res['usage_overhead_pct']}% overhead "
        f"({'OK' if res['usage_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_jobs_overhead() -> None:
    """Maintenance-plane tax on the cached-read path when the plane is
    idle (docs/jobs.md).

    Same paired-block harness as the observability stages; the stdin
    toggle flips the ``[jobs]`` module switch plus the master's policy
    loop (retuned to tick every pulse, far hotter than the production
    15s default), so "on" pays the volume server's claim polls, the
    heartbeat ``job_progress`` piggyback, and the policy evaluation
    over live telemetry — with no job ever submitted. The difference
    is exactly what an idle maintenance plane costs foreground reads.
    Acceptance (ISSUE 9): overhead < 2%."""
    t_off, t_on = _measure_plane_overhead("jobs")
    overhead = (t_on - t_off) / t_off
    res = {
        "jobs_overhead_pct": round(overhead * 100, 2),
        "jobs_read_us_off": round(t_off * 1e6, 1),
        "jobs_read_us_on": round(t_on * 1e6, 1),
        "jobs_overhead_ok": bool(overhead < 0.02),
    }
    log(f"jobs stage: cached read {res['jobs_read_us_off']}us "
        f"off / {res['jobs_read_us_on']}us on -> "
        f"{res['jobs_overhead_pct']}% overhead "
        f"({'OK' if res['jobs_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_ingress_overhead() -> None:
    """Ingress admission-control tax on the cached-read path
    (docs/ingress.md).

    Same paired-block harness as the observability stages; the stdin
    toggle flips ``httpserver.configure(enabled=...)``, so "on" pays
    the admission gate on every request (requests counter, deadline
    parse, pressure probe against the dispatch queue) while "off"
    takes the gate's single-flag fast path. The shared server core —
    bounded worker pool, keep-alive parking — runs identically under
    both modes, so the difference is the per-request admission cost.
    Acceptance (ISSUE 10): overhead < 2%."""
    t_off, t_on = _measure_plane_overhead("ingress")
    overhead = (t_on - t_off) / t_off
    res = {
        "ingress_overhead_pct": round(overhead * 100, 2),
        "ingress_read_us_off": round(t_off * 1e6, 1),
        "ingress_read_us_on": round(t_on * 1e6, 1),
        "ingress_overhead_ok": bool(overhead < 0.02),
    }
    log(f"ingress stage: cached read {res['ingress_read_us_off']}us "
        f"off / {res['ingress_read_us_on']}us on -> "
        f"{res['ingress_overhead_pct']}% overhead "
        f"({'OK' if res['ingress_overhead_ok'] else 'OVER BUDGET'})")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_scrub_overhead() -> None:
    """Paced-scrub foreground tax on the cached-read path
    (docs/robustness.md "Scrub & repair").

    Same paired-block harness as the observability stages; the stdin
    toggle starts/stops a background thread CRC-walking the served
    volume under the production token-bucket pacer (8 MiB/s), so the
    difference is the steady-state cost a client read pays while a
    scrub pass is in flight — the number the pacer exists to bound.
    A second, in-process measurement scrubs a synthetic volume
    UNPACED for the raw verification bandwidth (``scrub_gibps``),
    the ceiling the pacer throttles down from.
    Acceptance (ISSUE 20): paced overhead < 5%."""
    import shutil
    import tempfile

    from seaweedfs_tpu.storage import scrubber
    from seaweedfs_tpu.storage.volume import generate_synthetic_volume

    t_off, t_on = _measure_plane_overhead("scrub")
    overhead = (t_on - t_off) / t_off

    tmp = tempfile.mkdtemp(prefix="bench_scrub_raw_")
    try:
        svol = generate_synthetic_volume(
            os.path.join(tmp, "5"), 5, n_needles=256,
            avg_size=128 * 1024, seed=11)
        t0 = time.perf_counter()
        raw = scrubber.scrub_volume(svol)
        dt = time.perf_counter() - t0
        svol.close()
        if raw["corrupt"]:
            raise RuntimeError("scrub flagged a pristine volume")
        gibps = raw["bytes"] / dt / (1 << 30)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    res = {
        "scrub_overhead_pct": round(overhead * 100, 2),
        "scrub_read_us_off": round(t_off * 1e6, 1),
        "scrub_read_us_on": round(t_on * 1e6, 1),
        "scrub_overhead_ok": bool(overhead < 0.05),
        "scrub_gibps": round(gibps, 3),
        "scrub_raw_mib": round(raw["bytes"] / MIB, 1),
    }
    log(f"scrub stage: cached read {res['scrub_read_us_off']}us "
        f"off / {res['scrub_read_us_on']}us on -> "
        f"{res['scrub_overhead_pct']}% overhead "
        f"({'OK' if res['scrub_overhead_ok'] else 'OVER BUDGET'}); "
        f"raw verify {res['scrub_gibps']} GiB/s over "
        f"{res['scrub_raw_mib']} MiB")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_ckpt() -> None:
    """Checkpoint & dataloader workload plane (docs/workloads.md).

    One in-process cluster (master + volume + filer + S3 gateway) with
    the global chunk cache deliberately small in memory and backed by
    a disk tier, so the sequential-scan pass really runs over the disk
    tier. Four measured passes on 8 virtual CPU devices:

    1. sharded checkpoint save (4 x 16 MiB (dp,sp) params) —
       ``ckpt_save_gibps``;
    2. restore through manifest-driven HTTP range reads —
       ``ckpt_restore_gibps`` plus ``ckpt_ttfs_s`` (time from restore
       start to the first shard byte landing);
    3. dataloader epoch scans over cold 1 MiB objects, synchronous
       (depth 0) vs bounded prefetch (depth 4) —
       ``loader_scan_gibps`` / ``loader_scan_sync_gibps``;
    4. sequential 256 KiB ranged-GET scans of cold multi-MiB objects
       with the gateway's read-ahead on vs off —
       ``readahead_ratio`` (the ISSUE's >= 1.5x acceptance bar; on a
       shared-core CPU host the ratio is reported honestly, not
       asserted, like the virtual-mesh ratio)."""
    import shutil
    import socket
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seaweedfs_tpu.cache import chunk_cache as chunk_cache_mod
    from seaweedfs_tpu.ckpt import (CheckpointStore, GatewayClient,
                                    ObjectLoader)
    from seaweedfs_tpu.cluster.filer_server import FilerServer
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.filer import Filer
    from seaweedfs_tpu.gateway.s3 import S3Gateway
    from seaweedfs_tpu.parallel.mesh import make_mesh
    from seaweedfs_tpu.storage.store import Store

    def fp() -> int:
        for _ in range(50):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                p = s.getsockname()[1]
            if p + 10000 <= 65535:
                try:
                    with socket.socket() as s2:
                        s2.bind(("127.0.0.1", p + 10000))
                    return p
                except OSError:
                    continue
        raise RuntimeError("no free port pair")

    tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
    # small memory tier + real disk tier: the scan working set below
    # does not fit in memory, so ranged blocks live on (and re-read
    # from) the disk tier
    chunk_cache_mod.configure_global(
        capacity_bytes=8 * MIB,
        disk_dir=os.path.join(tmp, "cachedisk"),
        disk_capacity_bytes=1024 * MIB)
    vol_dir = os.path.join(tmp, "vol")
    os.makedirs(vol_dir)
    master = MasterServer(port=fp(), volume_size_limit_mb=256,
                          pulse_seconds=0.2, seed=5).start()
    vs = VolumeServer(Store([vol_dir], max_volumes=16), port=fp(),
                      master_url=master.url, pulse_seconds=0.2).start()
    deadline = time.time() + 15
    while time.time() < deadline and not master.topology.nodes:
        time.sleep(0.05)
    filer = FilerServer(Filer(), port=fp(),
                        master_url=master.url).start()
    gw = S3Gateway(filer.url, port=fp()).start()
    try:
        # ---- pass 1+2: sharded checkpoint save / restore ----
        mesh = make_mesh()
        rng = np.random.default_rng(11)
        tree = {}
        for i in range(4):
            host = rng.standard_normal((2048, 2048)).astype(np.float32)
            tree[f"w{i}"] = jax.device_put(
                jnp.asarray(host), NamedSharding(mesh, P("dp", "sp")))
        ckpt_bytes = sum(np.asarray(v).nbytes for v in tree.values())

        st = CheckpointStore(gw.url, bucket="bench-ckpt")
        t0 = time.perf_counter()
        st.save("step-1", tree)
        t_save = time.perf_counter() - t0

        client = GatewayClient(gw.url)
        st2 = CheckpointStore(gw.url, bucket="bench-ckpt",
                              client=client)
        ttfs = [None]
        orig_get_range = client.get_range

        def timed_get_range(*a, **kw):
            data = orig_get_range(*a, **kw)
            if ttfs[0] is None:
                ttfs[0] = time.perf_counter() - t0
            return data

        client.get_range = timed_get_range
        t0 = time.perf_counter()
        out = st2.restore("step-1", mesh=mesh)
        t_restore = time.perf_counter() - t0
        for name, arr in out.items():
            if np.asarray(arr).tobytes() != \
                    np.asarray(tree[name]).tobytes():
                raise SystemExit(f"ckpt stage: restored {name} "
                                 f"differs from saved bytes")
        del out

        # ---- pass 3: dataloader scans (cold objects per depth) ----
        obj_bytes = MIB
        n_objs = 24
        client.ensure_bucket("bench-loader")
        payloads = {}
        for depth_tag in ("sync", "pre"):
            for i in range(n_objs):
                key = f"{depth_tag}/obj-{i:03d}"
                data = rng.integers(0, 256, obj_bytes,
                                    dtype=np.uint8).tobytes()
                payloads[key] = data
                client.put("bench-loader", key, data)
        loader_times = {}
        for depth_tag, depth in (("sync", 0), ("pre", 4)):
            loader = ObjectLoader(client, "bench-loader",
                                  prefix=depth_tag + "/",
                                  seed=3, prefetch_depth=depth)
            t0 = time.perf_counter()
            for key, data in loader.scan():
                if data != payloads[key]:
                    raise SystemExit(f"ckpt stage: loader returned "
                                     f"wrong bytes for {key}")
            loader_times[depth_tag] = time.perf_counter() - t0
        scan_bytes = n_objs * obj_bytes

        # ---- pass 4: sequential ranged-GET scan, readahead on/off --
        stream_bytes = 48 * MIB
        step = 256 * 1024
        client.ensure_bucket("bench-stream")
        for tag in ("off", "on"):
            client.put("bench-stream", f"stream-{tag}",
                       rng.integers(0, 256, stream_bytes,
                                    dtype=np.uint8).tobytes())
        ra_times = {}
        observe = gw._observe_stream
        for tag in ("off", "on"):
            if tag == "off":
                gw._observe_stream = lambda *a, **kw: None
            else:
                gw._observe_stream = observe
            t0 = time.perf_counter()
            for off in range(0, stream_bytes, step):
                client.get_range("bench-stream", f"stream-{tag}",
                                 off, min(step, stream_bytes - off))
            ra_times[tag] = time.perf_counter() - t0
        gw._observe_stream = observe

        res = {
            "ckpt_save_gibps": round(ckpt_bytes / GIB / t_save, 3),
            "ckpt_restore_gibps":
                round(ckpt_bytes / GIB / t_restore, 3),
            "ckpt_ttfs_s": round(ttfs[0], 4) if ttfs[0] else None,
            "loader_scan_gibps":
                round(scan_bytes / GIB / loader_times["pre"], 3),
            "loader_scan_sync_gibps":
                round(scan_bytes / GIB / loader_times["sync"], 3),
            "loader_prefetch_speedup":
                round(loader_times["sync"] / loader_times["pre"], 2),
            "readahead_scan_gibps":
                round(stream_bytes / GIB / ra_times["on"], 3),
            "readahead_off_scan_gibps":
                round(stream_bytes / GIB / ra_times["off"], 3),
            "readahead_ratio":
                round(ra_times["off"] / ra_times["on"], 2),
        }
        log(f"ckpt stage: save {res['ckpt_save_gibps']} GiB/s, "
            f"restore {res['ckpt_restore_gibps']} GiB/s "
            f"(ttfs {res['ckpt_ttfs_s']}s), loader "
            f"{res['loader_scan_gibps']} vs "
            f"{res['loader_scan_sync_gibps']} GiB/s "
            f"({res['loader_prefetch_speedup']}x), readahead "
            f"{res['readahead_scan_gibps']} vs "
            f"{res['readahead_off_scan_gibps']} GiB/s "
            f"({res['readahead_ratio']}x)")
        _persist(res)
        print(json.dumps(res), flush=True)
    finally:
        gw.stop()
        filer.stop()
        vs.stop()
        master.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def child_sim() -> None:
    """Master ceilings at simulated cluster scale (docs/simulation.md).

    300 simulated volume servers / 30k volumes drive one real
    in-process MasterServer through a zipfian traffic-shift wave and a
    rack-loss wave on a virtual clock, then measure the ingestion hot
    paths wall-clock: steady-state heartbeat sweeps (the
    unchanged-topology fast path), a full policy tick (the O(volumes)
    ``cluster_rows`` fold), and ranked ``/dir/lookup`` latency.
    Invariant failures fail the stage — these numbers are only worth
    persisting for a cluster that actually converged."""
    import logging

    from seaweedfs_tpu.sim import SimCluster, run_scenario

    # after the import: glog installs its handler at import time and
    # would override a level set before it
    logging.getLogger("seaweedfs_tpu").setLevel(logging.ERROR)

    cluster = SimCluster(nodes=300, volumes=30_000, seed=7)
    report = run_scenario(cluster, [
        {"wave": "traffic_shift", "hot_ticks": 8, "cool_ticks": 14,
         "ops": 4000},
        {"wave": "rack_loss", "outage_ticks": 5, "recovery_ticks": 6},
    ], log=log)
    if not report["ok"]:
        raise SystemExit(f"sim stage: invariant failures: "
                         f"{[w['problems'] for w in report['waves']]}")
    b = report["bench"]
    res = {
        "sim_nodes": report["nodes"],
        "sim_volumes": report["volumes"],
        "sim_heartbeats_per_second": b["heartbeats_per_second"],
        "sim_policy_tick_seconds": b["policy_tick_seconds"],
        "sim_lookup_p99_seconds": b["lookup_p99_seconds"],
        "sim_lookup_p50_seconds": b["lookup_p50_seconds"],
        "sim_unchanged_heartbeat_fraction": round(
            report["heartbeats_unchanged"]
            / max(1, report["heartbeats_total"]), 4),
        "sim_waves_ok": True,
    }
    log(f"sim stage: {res['sim_heartbeats_per_second']:.0f} hb/s, "
        f"policy tick {res['sim_policy_tick_seconds'] * 1e3:.1f}ms, "
        f"lookup p99 {res['sim_lookup_p99_seconds'] * 1e6:.0f}us at "
        f"{res['sim_nodes']} nodes / {res['sim_volumes']} volumes")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_mesh() -> None:
    """Sharded-mesh encode/rebuild throughput (docs/mesh.md).

    Encodes one synthetic volume through the single-device host path
    and again through the auto-factored (dp, sp) mesh spanning every
    local device, then rebuilds a lost-shard set through the same
    mesh. Any byte difference from the single-device reference fails
    the stage — a mesh number is only worth persisting for a mesh
    that writes the reference bytes. Needs a TPU; with one chip there
    is nothing to shard and the stage says so."""
    import hashlib
    import shutil
    import tempfile

    import jax
    import numpy as np

    from seaweedfs_tpu.parallel import mesh as mesh_mod
    from seaweedfs_tpu.pipeline import encode, pipe, rebuild
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files, superblock, volume

    on_acc = _require_tpu()
    n_dev = len(jax.devices())
    if n_dev < 2:
        res = {"mesh_devices": n_dev,
               "mesh_skipped": "one device: nothing to shard"}
        log(f"mesh stage: {res['mesh_skipped']}")
        _persist(res)
        print(json.dumps(res), flush=True)
        return
    dp, sp = mesh_mod._auto_factor(n_dev)
    size = (256 << 20) if on_acc else (16 << 20)
    scheme = EcScheme(10, 4, large_block_size=1 << 20,
                      small_block_size=1 << 17)
    pipe.configure(batch_bytes=8 << 20)
    work = tempfile.mkdtemp(prefix="bench-mesh-")
    try:
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()

        def make(name):
            base = f"{work}/{name}"
            with open(volume.dat_path(base), "wb") as f:
                f.write(superblock.SuperBlock().to_bytes())
                f.write(payload)
            return base

        def digest(base):
            h = hashlib.sha256()
            for i in range(scheme.total_shards):
                h.update(ec_files.shard_path(base, i).read_bytes())
            return h.hexdigest()

        single = make("single")
        t0 = time.perf_counter()
        encode.write_ec_files(single, scheme)
        single_dt = time.perf_counter() - t0
        ref = digest(single)

        meshed = make("mesh")
        lost = [0, 5, 13]
        with mesh_mod.scoped(f"{dp},{sp}"):
            t0 = time.perf_counter()
            encode.write_ec_files(meshed, scheme)
            mesh_dt = time.perf_counter() - t0
            if digest(meshed) != ref:
                raise SystemExit("mesh stage: mesh shards differ from "
                                 "the single-device reference")
            for i in lost:
                ec_files.shard_path(meshed, i).unlink()
            t0 = time.perf_counter()
            done = rebuild.rebuild_ec_files(meshed, scheme)
            rebuild_dt = time.perf_counter() - t0
        if sorted(done) != lost or digest(meshed) != ref:
            raise SystemExit("mesh stage: mesh rebuild diverged from "
                             "the single-device reference")

        gib = size / (1 << 30)
        rebuilt_gib = (len(lost) * scheme.shard_file_size(size + 8)
                       / (1 << 30))
        res = {
            "mesh_devices": n_dev,
            "mesh_dp": dp,
            "mesh_sp": sp,
            "mesh_encode_gibps": round(gib / mesh_dt, 3),
            "mesh_rebuild_gibps": round(rebuilt_gib / rebuild_dt, 3),
            "mesh_single_encode_gibps": round(gib / single_dt, 3),
            "mesh_vs_single_ratio": round(single_dt / mesh_dt, 3),
        }
        log(f"mesh stage: dp={dp} sp={sp} on {n_dev} devices — encode "
            f"{res['mesh_encode_gibps']} GiB/s "
            f"({res['mesh_vs_single_ratio']}x single-device "
            f"{res['mesh_single_encode_gibps']}), rebuild "
            f"{res['mesh_rebuild_gibps']} GiB/s")
        _persist(res)
        print(json.dumps(res), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def child_stream_stages() -> None:
    """Re-bank the streaming-encode stage breakdown with the flight
    recorder armed: the aggregate per-stage thread-seconds
    (``e2e_stream_stages``) pick up recorder-derived busy FRACTIONS of
    the recorded wall window plus a named bottleneck — the decomposed
    version of the headline 0.006 GiB/s figure (ISSUE 17)."""
    import numpy as np
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_jax
    from seaweedfs_tpu.pipeline import flight as flight_mod
    from seaweedfs_tpu.pipeline import pipe

    k, m = 10, 4
    s = 4 * MIB
    n_bufs, passes = 4, 3
    rng = np.random.default_rng(11)
    slabs = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
             for _ in range(n_bufs)]
    coefs = rs_jax.Encoder(k, m).parity_coefs

    def encode_fn(b):
        return rs_jax.apply_matrix(coefs, jnp.asarray(b))

    np.asarray(encode_fn(slabs[0]))  # compile out of the timed window
    flight_mod.arm()
    flight_mod.reset()
    stats = pipe.PipeStats()

    def batches():
        for _ in range(passes):
            for h in slabs:
                yield None, h

    t0 = time.perf_counter()
    n = pipe.run_pipeline(batches(), encode_fn, lambda *_: None,
                          stats=stats, kind="bench.stream_stages")
    dt = time.perf_counter() - t0
    in_bytes = n * k * s
    res = {
        "stream_stages_gibps": round(in_bytes / GIB / dt, 3),
        "e2e_stream_stages": stats.stage_seconds(),
    }
    try:
        ana = flight_mod.analyze()
        occ = ana.get("occupancy") or {}
        if occ.get("batches"):
            res["e2e_stream_occupancy"] = occ["busy_fraction"]
            res["e2e_stream_bottleneck"] = ana["bottleneck"]
            res["e2e_stream_waited_on"] = occ["waited_on"]
        trace_path = os.path.join(ARTIFACTS,
                                  "stream_stages_trace_r05.json")
        flight_mod.dump_trace(trace_path)
        res["stream_stages_trace"] = trace_path
    finally:
        flight_mod.disarm()
    log(f"stream stages: {in_bytes / GIB:.2f} GiB in {dt:.2f} s -> "
        f"{res['stream_stages_gibps']} GiB/s; occupancy "
        f"{res.get('e2e_stream_occupancy')} -> bottleneck "
        f"{res.get('e2e_stream_bottleneck')}")
    _persist(res)
    print(json.dumps(res), flush=True)


def child_flight_overhead() -> None:
    """Flight-recorder tax on the overlapped file-encode path.

    Same paired-block discipline as the other plane-overhead stages:
    alternating recorder-off/recorder-on rounds of a full overlapped
    encode (256 MiB on tmpfs, smaller on the slow container disk),
    per-round diffs, interquartile mean so scheduler spikes shed.
    Small batch bytes force many batches per encode — the recorder
    records ~20 events per batch, so this measures the ARMED hot-path
    cost, not one no-op branch. Acceptance (ISSUE 17): overhead < 2%."""
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.pipeline import flight as flight_mod
    from seaweedfs_tpu.pipeline import pipe
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files, superblock, volume

    size = 256 * MIB
    fast = _fast_tmpdir(need_bytes=int(2.6 * size) + 64 * MIB)
    if fast is None:
        size = 64 * MIB  # container disk: don't grind 256 MiB rounds
    scheme = EcScheme(10, 4, large_block_size=1 << 20,
                      small_block_size=1 << 17)
    # many batches per encode -> many recorded events per round
    pipe.configure(batch_bytes=8 * MIB, grouped_batch_bytes=4 * MIB)
    work = tempfile.mkdtemp(dir=fast, prefix="bench-flight-")
    try:
        base = os.path.join(work, "1")
        rng = np.random.default_rng(17)
        with open(volume.dat_path(base), "wb") as f:
            f.write(superblock.SuperBlock().to_bytes())
            f.write(rng.integers(0, 256, size, dtype=np.uint8)
                    .tobytes())

        def clean() -> None:
            for p in ([ec_files.shard_path(base, i)
                       for i in range(scheme.total_shards)]
                      + [ec_files.ecx_path(base),
                         ec_files.vif_path(base)]):
                if p.exists():
                    p.unlink()

        def one(armed: bool) -> float:
            if armed:
                flight_mod.arm()
                flight_mod.reset()
            else:
                flight_mod.disarm()
            clean()
            t0 = time.perf_counter()
            encode_mod.write_ec_files(base, scheme)
            return time.perf_counter() - t0

        one(False)  # warm: native build, jit compile, page cache
        rounds, times = 8, {"off": [], "on": []}
        diffs = []
        for rnd in range(rounds):
            order = (False, True) if rnd % 2 == 0 else (True, False)
            rtime = {}
            for armed in order:
                key = "on" if armed else "off"
                rtime[key] = one(armed)
                times[key].append(rtime[key])
            diffs.append(rtime["on"] - rtime["off"])
        flight_mod.disarm()
        diffs.sort()
        q = len(diffs) // 4
        delta = statistics.fmean(diffs[q:len(diffs) - q])
        t_off = statistics.median(times["off"])
        overhead = delta / t_off
        res = {
            "flight_overhead_pct": round(overhead * 100, 2),
            "flight_encode_s_off": round(t_off, 3),
            "flight_encode_s_on": round(t_off + delta, 3),
            "flight_encode_mib": size // MIB,
            "flight_encode_fs": "tmpfs" if fast else "disk",
            "flight_overhead_ok": bool(overhead < 0.02),
        }
        log(f"flight stage: overlapped {size // MIB} MiB encode "
            f"{res['flight_encode_s_off']}s off / "
            f"{res['flight_encode_s_on']}s on -> "
            f"{res['flight_overhead_pct']}% overhead "
            f"({'OK' if res['flight_overhead_ok'] else 'OVER BUDGET'})")
        _persist(res)
        print(json.dumps(res), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def child_racecheck_overhead() -> None:
    """Lockset race-checker tax on the overlapped file-encode path.

    Paired-block discipline (see child_flight_overhead): alternating
    disarmed/armed rounds of a full overlapped encode, per-round
    diffs, interquartile mean. Armed rounds run record mode exactly as
    the tier-1 conftest does — every PipeStats/pool/controller
    attribute write goes through the Eraser state machine, with held
    locks snapshotted off the steady-state path. Each round builds
    fresh pipeline objects, so disarmed rounds carry no instrumented
    classes from earlier armed rounds. Acceptance (ISSUE 18):
    overhead < 5%, and the DISARMED register() fast path — what every
    production construction site pays — must be nanoseconds (a single
    module-flag test), reported as racecheck_disarmed_register_ns.
    """
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.pipeline import pipe
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files, superblock, volume
    from seaweedfs_tpu.util import lockcheck, racecheck

    size = 256 * MIB
    fast = _fast_tmpdir(need_bytes=int(2.6 * size) + 64 * MIB)
    if fast is None:
        size = 64 * MIB  # container disk: don't grind 256 MiB rounds
    scheme = EcScheme(10, 4, large_block_size=1 << 20,
                      small_block_size=1 << 17)
    # many batches per encode -> many tracked stats/pool writes
    pipe.configure(batch_bytes=8 * MIB, grouped_batch_bytes=4 * MIB)

    # Disarmed fast path, measured BEFORE anything arms the checker:
    # production code calls register() unconditionally at construction.
    assert not racecheck.enabled()
    probe = pipe.PipeStats()
    n = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n):
        racecheck.register(probe, "bench.probe")
    disarmed_ns = (time.perf_counter() - t0) / n * 1e9

    work = tempfile.mkdtemp(dir=fast, prefix="bench-racecheck-")
    try:
        base = os.path.join(work, "1")
        rng = np.random.default_rng(18)
        with open(volume.dat_path(base), "wb") as f:
            f.write(superblock.SuperBlock().to_bytes())
            f.write(rng.integers(0, 256, size, dtype=np.uint8)
                    .tobytes())

        def clean() -> None:
            for p in ([ec_files.shard_path(base, i)
                       for i in range(scheme.total_shards)]
                      + [ec_files.ecx_path(base),
                         ec_files.vif_path(base)]):
                if p.exists():
                    p.unlink()

        def one(armed: bool) -> float:
            if armed:
                racecheck.install()     # record mode, as in conftest
                racecheck.reset()
            else:
                racecheck.uninstall()
                lockcheck.uninstall()
            clean()
            t0 = time.perf_counter()
            encode_mod.write_ec_files(base, scheme)
            return time.perf_counter() - t0

        one(False)  # warm: native build, jit compile, page cache
        rounds, times = 8, {"off": [], "on": []}
        diffs = []
        for rnd in range(rounds):
            order = (False, True) if rnd % 2 == 0 else (True, False)
            rtime = {}
            for armed in order:
                key = "on" if armed else "off"
                rtime[key] = one(armed)
                times[key].append(rtime[key])
            diffs.append(rtime["on"] - rtime["off"])
        racecheck.uninstall()
        lockcheck.uninstall()
        races = len(racecheck.races())
        diffs.sort()
        q = len(diffs) // 4
        delta = statistics.fmean(diffs[q:len(diffs) - q])
        t_off = statistics.median(times["off"])
        overhead = delta / t_off
        res = {
            "racecheck_overhead_pct": round(overhead * 100, 2),
            "racecheck_encode_s_off": round(t_off, 3),
            "racecheck_encode_s_on": round(t_off + delta, 3),
            "racecheck_encode_mib": size // MIB,
            "racecheck_encode_fs": "tmpfs" if fast else "disk",
            "racecheck_disarmed_register_ns": round(disarmed_ns, 1),
            "racecheck_races_seen": races,
            "racecheck_overhead_ok": bool(overhead < 0.05),
        }
        log(f"racecheck stage: overlapped {size // MIB} MiB encode "
            f"{res['racecheck_encode_s_off']}s off / "
            f"{res['racecheck_encode_s_on']}s on -> "
            f"{res['racecheck_overhead_pct']}% overhead, disarmed "
            f"register {res['racecheck_disarmed_register_ns']}ns "
            f"({'OK' if res['racecheck_overhead_ok'] else 'OVER BUDGET'})")
        _persist(res)
        print(json.dumps(res), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    if "--child-core" in sys.argv:
        child_core()
    elif "--child-config3" in sys.argv:
        child_config3()
    elif "--child-config5" in sys.argv:
        child_config5()
    elif "--child-cache" in sys.argv:
        child_cache()
    elif ("--child-trace-overhead" in sys.argv
          or "--trace-overhead" in sys.argv):
        child_trace_overhead()
    elif ("--child-telemetry-overhead" in sys.argv
          or "--telemetry-overhead" in sys.argv):
        child_telemetry_overhead()
    elif ("--child-fault-overhead" in sys.argv
          or "--fault-overhead" in sys.argv):
        child_fault_overhead()
    elif ("--child-profile-overhead" in sys.argv
          or "--profile-overhead" in sys.argv):
        child_profile_overhead()
    elif ("--child-usage-overhead" in sys.argv
          or "--usage-overhead" in sys.argv):
        child_usage_overhead()
    elif ("--child-jobs-overhead" in sys.argv
          or "--jobs-overhead" in sys.argv):
        child_jobs_overhead()
    elif ("--child-ingress-overhead" in sys.argv
          or "--ingress-overhead" in sys.argv):
        child_ingress_overhead()
    elif ("--child-scrub-overhead" in sys.argv
          or "--scrub-overhead" in sys.argv):
        child_scrub_overhead()
    elif "--child-sim" in sys.argv:
        child_sim()
    elif "--child-ckpt" in sys.argv:
        child_ckpt()
    elif "--child-mesh" in sys.argv:
        child_mesh()
    elif ("--child-stream-stages" in sys.argv
          or "--stream-stages" in sys.argv):
        child_stream_stages()
    elif ("--child-flight-overhead" in sys.argv
          or "--flight-overhead" in sys.argv):
        child_flight_overhead()
    elif ("--child-racecheck-overhead" in sys.argv
          or "--racecheck-overhead" in sys.argv):
        child_racecheck_overhead()
    else:
        parent()
