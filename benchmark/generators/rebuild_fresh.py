"""``ec.rebuild`` again and again on one encoded volume, and no two rounds
lose the same shards: what ``rebuild_loop`` does (its ``prepare``,
``rebuild``, ``keep_restored`` and ``verify``, taken from it), with every
round's loss pattern drawn from ``--seed``, without replacement, from all
``lost_per_round``-subsets of the volume's shards (1001 four-subsets of
fourteen). The warm-up's round takes one further pattern of the same draw,
which the window never uses: the server has then rebuilt once, and has seen
none of the window's patterns.

This is the rate an operator gets after a node loss: upstream's
``ec.rebuild`` walks every EC volume with missing shards, and ``ec.balance``
has placed each volume's shards on its own, so the lost holders took another
shard set from each volume. One volume, lost and repaired again with a new
pattern, stands for the node's worth of volumes.

The configuration's guarantee ``pattern`` is held against the server's own
count of the patterns it has met (``codec.decode_patterns``), which the
configuration ``requires``: a program without it gives no result here.
"""

from __future__ import annotations

import time
from itertools import combinations

import numpy as np

import rebuild_loop
from cluster import BenchFailure
from encode_stream import encode
from rebuild_loop import (GIB, VID, keep_restored, max_volumes,  # noqa: F401
                          prepare, rebuild)
from reference import at_least, exactly


def draw_patterns(ctx, count: int) -> list:
    """``count`` different loss patterns, the seed's."""
    lay = ctx.layout
    every = list(combinations(range(lay.k + lay.m),
                              ctx.params["lost_per_round"]))
    rng = np.random.default_rng([ctx.seed, 32])
    return [list(every[i]) for i in rng.permutation(len(every))[:count]]


def held_to_its_own_count(ctx) -> None:
    """The configuration's ``requires``: what the server's ``/debug/vars``
    has to hold for the guarantee ``pattern`` to be held against the
    server. A program that does not count the patterns it has met cannot
    run this configuration, and the run ends here, before any command,
    with no result."""
    dv = ctx.cluster.debug_vars()
    missing = [f"{section}.{key}"
               for section, keys in ctx.cfg["requires"].items()
               for key in keys if key not in (dv.get(section) or {})]
    if missing:
        raise BenchFailure(
            f"configuration {ctx.cfg['name']} requires {missing} in the "
            f"server's /debug/vars, and this program has none: it cannot "
            f"say which loss patterns it has met")


def setup(ctx, state) -> None:
    held_to_its_own_count(ctx)
    ctx.cluster.wait_volumes(1)
    encode(ctx, VID)
    *state["patterns"], state["warmup_pattern"] = draw_patterns(
        ctx, ctx.params["max_rounds"] + 1)
    state["warmup_seconds"] = rebuild(ctx, state["warmup_pattern"])


def window(ctx, state, seconds: float) -> dict:
    lay = ctx.layout
    shard_bytes = lay.rows(state["infos"][VID].dat_size) * lay.small
    attempted = failed = 0
    per_command = []
    t0 = t_end = time.perf_counter()
    for gone in state["patterns"]:
        if time.perf_counter() - t0 >= seconds:
            break
        attempted += 1
        try:
            per_command.append(rebuild(ctx, gone))
        except BenchFailure as e:
            failed += 1
            state.setdefault("errors", []).append(str(e)[:500])
            continue
        t_end = time.perf_counter()
        keep_restored(ctx, state, gone)
        ctx.tick()
    elapsed = max(t_end - t0, 1e-9)
    done = len(state["rounds"])
    return {"metrics": {ctx.params["metric"]:
                        done * lay.k * shard_bytes / GIB / elapsed},
            "attempted": attempted, "failed": failed,
            "window_seconds": elapsed, "busy_seconds": sum(per_command),
            "lost_shards": ctx.params["lost_per_round"],
            "detail": {"commands": done, "shard_bytes": shard_bytes,
                       "warmup_command_seconds": state["warmup_seconds"],
                       "warmup_pattern": state["warmup_pattern"],
                       "patterns": state["rounds"],
                       "command_seconds": [round(s, 4) for s in per_command],
                       "errors": state.get("errors", [])[:3]}}


def verify(ctx, state) -> tuple[dict, list]:
    """``rebuild_loop``'s comparison of every restored file of every
    round, and the patterns themselves: none met twice by the server
    (the warm-up's counted), as many different ones as rounds completed,
    by the generator's draw and by the server's own count over the
    window (``codec.decode_patterns``, after minus before)."""
    compared, problems = rebuild_loop.verify(ctx, state)
    met = [tuple(state["warmup_pattern"])] + [tuple(g)
                                              for g in state["rounds"]]
    repeated = len(met) - len(set(met))
    if repeated:
        problems.append(f"{repeated} round(s) met a pattern the server "
                        f"had seen")
    compared["patterns_repeated"] = exactly(repeated, 0)
    compared["patterns_distinct"] = at_least(len(set(met[1:])),
                                             len(state["rounds"]))
    new_to_server = (ctx.after["codec"]["decode_patterns"]
                     - ctx.before["codec"]["decode_patterns"])
    if new_to_server < len(state["rounds"]):
        problems.append(f"the server met {new_to_server} new pattern(s) "
                        f"in {len(state['rounds'])} round(s)")
    compared["patterns_new_to_server"] = at_least(new_to_server,
                                                  len(state["rounds"]))
    return compared, problems
