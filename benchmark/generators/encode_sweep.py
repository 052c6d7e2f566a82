"""``ec.encode -collection c -fullPercent p -quietFor d``, one collection
after another: one client, closed loop, the cluster shell's sweep form,
as upstream's ``master.toml`` maintenance script seals a cold tier.

Every collection holds the configuration's ``population``: volumes that
qualify (over ``full_percent`` of the master's volume size limit, quiet
for longer than ``quiet_for``), an under-full one and a full one written
just now. A sweep has to seal exactly the first kind and leave the others
as they were; which those are is worked out here, from the sizes written
and the mtimes set, by upstream's rule, and never asked of the program.

Collection 0 is the warm-up's; the window works through the others. It
closes when the set is exhausted or, if a sweep is still to start then,
at ``--seconds``; the rate is the ``.dat`` bytes of the volumes that
completed sweeps sealed over the seconds from the window's start to the
last reply, under the name the traffic file gives (``metric``).

About ``DISTINCT_INPUT_BYTES`` of the inputs are written, whole
collections; the others are further links to those files, place by place
(``encode_stream`` says why). Links share an mtime, and a place has one
kind in every collection.
"""

from __future__ import annotations

import hashlib
import os
import re
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import replace
from multiprocessing import get_context
from pathlib import Path

import numpy as np

import encode_stream
import volumes
from cluster import BenchFailure
from encode_stream import DISTINCT_INPUT_BYTES, GIB
from reference import at_most, exactly

MIB = 1 << 20
EC_EXTS = [f".ec{i:02d}" for i in range(14)] + [".ecx", ".vif"]
_UNITS = {"s": 1, "m": 60, "h": 3600}


def limit_mb(cfg: dict) -> int:
    """The master's volume size limit: one MB for every 1,000,000 bytes
    of ``volume_bytes`` (30 for the configuration as it stands, 3 for
    the tests' tenth of it)."""
    return max(1, cfg["volume_bytes"] // 1_000_000)


def quiet_seconds(text: str) -> int:
    return sum(int(n) * _UNITS[u]
               for n, u in re.findall(r"(\d+)([smh])", text))


def qualifies(size: int, modified: float, cfg: dict, now: float) -> bool:
    """Upstream's ``collectVolumeIdsForEcEncode``, for a volume of the
    collection swept."""
    sweep = cfg["sweep"]
    return modified + quiet_seconds(sweep["quiet_for"]) < now \
        and size > sweep["full_percent"] / 100.0 * limit_mb(cfg) * MIB


def places(cfg: dict, params: dict) -> list:
    """One entry per volume of a collection: (kind, target bytes, age).
    The population's counts are scaled by what ``set_bytes`` gives a
    collection of the window, sizes are drawn from the layout seed and
    the place: every collection and every run has the same."""
    limit = limit_mb(cfg) * MIB
    # a volume comes out up to a record short of its target: one drawn
    # on the threshold itself would fall under it
    floor = int(cfg["sweep"]["full_percent"] / 100.0 * limit) + 4096
    pop = cfg["population"]
    swept = sum(k["count"] * sum(k["percent_of_limit"]) / 200.0 * limit
                for k in pop if k["kind"] == "qualifying")
    scale = params["set_bytes"] / (params["collections"] * swept)
    out = []
    for k in pop:
        lo, hi = k["percent_of_limit"]
        for _ in range(max(1, round(k["count"] * scale))):
            rng = np.random.default_rng([cfg["layout_seed"], len(out)])
            size = int(rng.uniform(lo, hi) / 100.0 * limit)
            if lo >= cfg["sweep"]["full_percent"]:
                size = max(size, floor)
            out.append((k["kind"], size, k["modified_seconds_ago"]))
    return out


def digest(path: Path) -> str:
    h = hashlib.blake2b()
    with open(path, "rb") as f:
        while chunk := f.read(1 << 22):
            h.update(chunk)
    return h.hexdigest()


def prepare(ctx) -> dict:
    cfg, p = ctx.cfg, ctx.params
    # upstream's own setting, the one way a `server` is told it
    cfg["server_toml"] += f"\n[master]\nvolumeSizeLimitMB = {limit_mb(cfg)}\n"
    plan = places(cfg, p)
    names = [f"{cfg['collection']}{c}" for c in range(p["collections"] + 1)]
    data = ctx.workdir / "data"
    data.mkdir(parents=True, exist_ok=True)
    vid_of = {(c, i): c * len(plan) + i + 1
              for c in range(len(names)) for i in range(len(plan))}
    written = max(1, min(len(names), round(
        DISTINCT_INPUT_BYTES / cfg["volume_bytes"] / len(plan))))
    jobs = [(c, i) for c in range(written) for i in range(len(plan))]
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        futs = [ex.submit(volumes.write_volume, str(data), names[c],
                          vid_of[c, i], cfg["needle_mix"], plan[i][1],
                          cfg["layout_seed"], ctx.seed) for c, i in jobs]
        infos = {f.result().vid: f.result() for f in futs}
    now = time.time()
    for c, i in jobs:
        then = now - plan[i][2]
        os.utime(data / f"{names[c]}_{vid_of[c, i]}.dat", (then, then))
    for c in range(written, len(names)):
        for i in range(len(plan)):
            src, vid = vid_of[c % written, i], vid_of[c, i]
            for ext in (".dat", ".idx"):
                os.link(data / f"{names[c % written]}_{src}{ext}",
                        data / f"{names[c]}_{vid}{ext}")
            infos[vid] = replace(infos[src], vid=vid, collection=names[c])
    # ec.encode deletes the .dat when it is done; a second link keeps the
    # sealed bytes for the reference at no cost in disk
    sealed = ctx.workdir / "sealed"
    sealed.mkdir()
    modified, untouched = {}, {}
    for (c, i), vid in vid_of.items():
        path = data / f"{names[c]}_{vid}.dat"
        os.link(path, sealed / f"{vid}.dat")
        modified[vid] = path.stat().st_mtime
        if plan[i][0] != "qualifying" and c < written:
            untouched[c, i] = digest(path)
    return {"infos": infos, "names": names, "vid_of": vid_of,
            "plan": plan, "modified": modified, "untouched": untouched,
            "written": written,
            "sealed": sealed, "swept": [], "done": []}


def max_volumes(ctx, state) -> int:
    return len(state["infos"]) + 8


def expected(ctx, state, c: int, now: float) -> set:
    """The volumes of collection ``c`` that a sweep at ``now`` seals."""
    return {vid for (cc, _), vid in state["vid_of"].items() if cc == c
            and qualifies(state["infos"][vid].dat_size,
                          state["modified"][vid], ctx.cfg, now)}


def sweep(ctx, name: str) -> tuple:
    """(seconds, volumes the reply says it sealed) of one sweep."""
    s = ctx.cfg["sweep"]
    seconds, reply = ctx.shell.run(
        f"ec.encode -collection {name} -fullPercent {s['full_percent']} "
        f"-quietFor {s['quiet_for']}", timeout=ctx.params["command_timeout_s"])
    if f"ec.encode collection '{name}': sealed " not in reply:
        raise BenchFailure(f"ec.encode -collection {name} said "
                           f"{reply[-500:]!r}")
    return seconds, [int(v) for v in re.findall(
        r"^ec\.encode volume (\d+): \d+ shards over", reply, re.M)]


def setup(ctx, state) -> None:
    ctx.cluster.wait_volumes(len(state["infos"]))
    state["warmup_seconds"], _ = sweep(ctx, state["names"][0])
    state["swept"].append((0, time.time()))


def window(ctx, state, seconds: float) -> dict:
    infos = state["infos"]
    attempted = failed = nbytes = 0
    per_command = []
    t0 = t_end = time.perf_counter()
    for c in range(1, len(state["names"])):
        if time.perf_counter() - t0 >= seconds:
            break
        attempted += 1
        now = time.time()
        try:
            took, said = sweep(ctx, state["names"][c])
        except BenchFailure as e:
            failed += 1
            state.setdefault("errors", []).append(str(e)[:500])
            continue
        t_end = time.perf_counter()
        per_command.append(took)
        state["swept"].append((c, now))
        state["done"] += said
        nbytes += sum(infos[vid].dat_size for vid in said)
        ctx.tick()
    elapsed = max(t_end - t0, 1e-9)
    return {"metrics": {ctx.params["metric"]: nbytes / GIB / elapsed},
            "attempted": attempted, "failed": failed,
            "window_seconds": elapsed, "busy_seconds": sum(per_command),
            "detail": {"commands": len(per_command),
                       "volumes_sealed": len(state["done"]),
                       "volumes_held": len(infos),
                       "warmup_command_seconds": state["warmup_seconds"],
                       "dat_bytes": nbytes,
                       "set": len(state["names"]) - 1,
                       "set_exhausted":
                           attempted == len(state["names"]) - 1,
                       "command_seconds": [round(s, 4) for s in per_command],
                       "errors": state.get("errors", [])[:3]}}


class _Bases:
    """``Cluster.base`` over several collections: ``encode_stream.verify``
    asks for a volume's files under the configuration's one name."""

    def __init__(self, cluster, collection_of: dict):
        self.cluster, self.collection_of = cluster, collection_of

    def base(self, _collection: str, vid: int) -> Path:
        return self.cluster.base(self.collection_of[vid], vid)


def verify(ctx, state) -> tuple[dict, list]:
    """Every volume the window's sweeps sealed, as ``encode_stream``
    holds an encoded volume to the reference; then the selection: the
    sealed and the untouched volumes of every completed sweep (the
    warm-up's too) against what upstream's rule gives here; and one
    generate call per sweep."""
    infos, names = state["infos"], state["names"]
    view = replace(ctx, cluster=_Bases(
        ctx.cluster, {vid: info.collection for vid, info in infos.items()}))
    compared, problems = encode_stream.verify(view, state)
    wrongly_sealed = wrongly_skipped = differing = 0
    for c, now in state["swept"]:
        want = expected(ctx, state, c, now)
        for (cc, i), vid in state["vid_of"].items():
            if cc != c:
                continue
            base = ctx.cluster.base(names[c], vid)
            ec = [ext for ext in EC_EXTS if os.path.exists(f"{base}{ext}")]
            plain = os.path.exists(f"{base}.dat") \
                and os.path.exists(f"{base}.idx")
            if vid in want:
                if plain or len(ec) != len(EC_EXTS):
                    wrongly_skipped += 1
                    problems.append(f"volume {vid} ({state['plan'][i][0]})"
                                    f" qualifies and is not sealed")
                continue
            if ec or not plain:
                wrongly_sealed += 1
                problems.append(f"volume {vid} ({state['plan'][i][0]}) "
                                f"does not qualify and has {ec or 'no .dat'}")
            elif digest(Path(f"{base}.dat")) \
                    != state["untouched"][c % state["written"], i]:
                differing += 1
                problems.append(f"volume {vid}: .dat of an untouched "
                                f"volume changed")
    said = set(state["done"])
    want = set().union(*(expected(ctx, state, c, now)
                         for c, now in state["swept"] if c))
    sweeps = max(1, sum(1 for c, _ in state["swept"] if c))
    calls = ctx.after["pipeline"].get("step_generate_calls", 0) \
        - ctx.before["pipeline"].get("step_generate_calls", 0)
    compared.update(
        volumes_wrongly_sealed=exactly(wrongly_sealed, 0),
        volumes_wrongly_skipped=exactly(wrongly_skipped, 0),
        skipped_dat_differing=exactly(differing, 0),
        replies_differing=exactly(len(said ^ want), 0),
        rpcs_per_sweep=at_most(calls / sweeps, 1))
    return compared, problems
