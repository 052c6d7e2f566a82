"""A cold tier repaired as one job: ``ec.rebuild -force`` over a
collection, the volumes' restores coalesced into shared device batches
on the rebuilder (``pipeline/rebuild.rebuild_volumes`` behind
``VolumeEcShardsRebuildBatch``).

First the packed reconstruct alone, on shard files written here with
the plain oracle ``ops/rs_ref.py`` (seeded): byte for byte the oracle's
and what one ``rebuild_ec_files`` per volume writes, over volumes of one
to five rows and a ragged tail, two loss patterns in one batch (their
slabs apart), survivors from files, from streams and from both, 1 to 4
shards lost, a stream that fails taking its volume and no other. Then
the shell's walk on the rack of ``test_ec_spread.py`` after a server was
replaced: one batch rpc for the collection, every restored file the
benchmark reference's, every restored and index file past its barrier
before the first mount, a fault in one volume's stream leaving nothing
of that volume on the rebuilder while the others are restored, healthy
volumes putting nothing there, and ``-volumeId`` keeping its rpc.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.pipeline import batch as batch_mod
from seaweedfs_tpu.pipeline import flight, pipe
from seaweedfs_tpu.pipeline import rebuild as rebuild_mod
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.util import faults, tracing

from test_ec_spread import (COL, ROW, SCHEME, TOTAL,  # noqa: F401
                            first_hit, racks, small_rows)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

BLOCK = 64 * 1024
#: large rows of four small blocks, so a volume of five rows has one
PACKED = EcScheme(10, 4, large_block_size=4 * BLOCK,
                  small_block_size=BLOCK)
K = PACKED.data_shards


# --------------------------------------------------------------------------
# the packed reconstruct, on files
# --------------------------------------------------------------------------

def shard_set(size: int, seed: int) -> list:
    """The 14 shards of one volume: k seeded data shards and the
    oracle's parity."""
    rng = np.random.default_rng(seed)
    shards = [rng.integers(0, 256, size, dtype=np.uint8) for _ in range(K)]
    shards += [np.zeros(size, dtype=np.uint8) for _ in range(4)]
    ReferenceEncoder(K, 4).encode(shards)
    return shards


class FileFeed:
    """``StreamedSurvivors`` off files of another directory: each
    (volume, shard) read in order, as the volume server's chains read a
    stream; ``cut`` fails one volume's stream after its first piece."""

    def __init__(self, bases: dict, cut=None):
        self.bases, self.cut = bases, cut
        self._files, self._failed, self.pieces = {}, {}, 0
        self.closed = False

    def fill(self, pieces):
        for key, sid, view, last in pieces:
            if key in self._failed:
                continue
            self.pieces += 1
            if key == self.cut and self.pieces > 1:
                self._failed[key] = OSError("cut")
                continue
            f = self._files.get((key, sid))
            if f is None:
                f = self._files[key, sid] = open(
                    ec_files.shard_path(self.bases[key], sid), "rb")
            assert f.readinto(memoryview(view)) == view.size
            if last:
                assert f.read(1) == b""
                self._files.pop((key, sid)).close()
        return lambda: None

    def failed(self):
        return dict(self._failed)

    def close(self):
        self.closed = True
        for f in self._files.values():
            f.close()


def lay_out(tmp_path, sizes: dict, lost: dict, streamed: dict):
    """Per volume: its shards (the oracle's), a directory holding the
    survivors that are files there, and one holding those the feed
    streams. ``streamed[key]`` is how many of the first k survivors come
    by the feed (the lowest ids)."""
    vols = {}
    for key, size in sizes.items():
        shards = shard_set(size, seed=key)
        here, there = tmp_path / f"here{key}", tmp_path / f"there{key}"
        here.mkdir()
        there.mkdir()
        base, far = here / f"{COL}_{key}", there / f"{COL}_{key}"
        survive = [s for s in range(14) if s not in lost[key]]
        fed = survive[:streamed[key]]
        for s in survive:
            shards[s].tofile(ec_files.shard_path(far if s in fed else base,
                                                 s))
        vols[key] = (base, far, shards, fed)
    return vols


def repairs_of(vols, lost) -> list:
    return [rebuild_mod.plan_repair(key, base, PACKED, lost[key], fed,
                                    dat_size=0)
            if not fed else rebuild_mod.Repair(
                key, base, PACKED, tuple(sorted(
                    s for s in range(14) if s not in lost[key])[:K]),
                tuple(lost[key]), shards[0].size, frozenset(fed))
            for key, (base, _far, shards, fed) in vols.items()]


#: (rows of each volume, shards each lost): five volumes of 1, 2, 3 and 5
#: rows and a ragged one; two loss patterns in one batch
CASES = {
    "one_lost": {1: [7], 2: [7], 3: [7], 4: [7], 5: [7]},
    "two_lost": {1: [0, 13], 2: [0, 13], 3: [0, 13], 4: [0, 13],
                 5: [0, 13]},
    "three_lost_two_patterns": {1: [1, 6, 11], 2: [1, 6, 11],
                                3: [2, 3, 12], 4: [1, 6, 11],
                                5: [2, 3, 12]},
    "four_lost": {1: [1, 6, 11, 13], 2: [1, 6, 11, 13], 3: [1, 6, 11, 13],
                  4: [1, 6, 11, 13], 5: [1, 6, 11, 13]},
}
SIZES = {1: BLOCK, 2: 2 * BLOCK, 3: 3 * BLOCK, 4: 5 * BLOCK,
         5: 2 * BLOCK + 1000}


@pytest.mark.parametrize("source", ["files", "streams", "both"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_packed_restore_is_the_oracles_and_one_rebuild_per_volume(
        tmp_path, monkeypatch, case, source):
    lost = CASES[case]
    streamed = {key: {"files": 0, "streams": K, "both": key % 4 * 3}[source]
                for key in SIZES}
    vols = lay_out(tmp_path, SIZES, lost, streamed)
    plans = []
    real_plan = batch_mod.plan_packed_batches

    def plan(*a, **kw):
        plans.append(list(real_plan(*a, **kw)))
        return plans[-1]
    monkeypatch.setattr(batch_mod, "plan_packed_batches", plan)
    before = pipe.debug_payload()
    feed = FileFeed({key: v[1] for key, v in vols.items()}) \
        if source != "files" else None
    repairs = repairs_of(vols, lost)
    # three rows a slab: a volume's rows cross from one slab to the next
    failed = rebuild_mod.rebuild_volumes(repairs, remote=feed,
                                         slab_bytes=3 * K * BLOCK,
                                         durable=True)
    after = pipe.debug_payload()
    assert failed == {}
    assert feed is None or feed.closed
    for key, (base, _far, shards, fed) in vols.items():
        for s in lost[key]:
            assert np.array_equal(np.fromfile(ec_files.shard_path(base, s),
                                              dtype=np.uint8), shards[s])
        # nothing else was written beside the survivors that lay there
        assert sorted(ec_files.present_shards(base, 14)) == sorted(
            set(range(14)) - set(fed))
    # a slab never mixes two patterns, and there is a run per pattern
    patterns = {r.key: r.pattern for r in repairs}
    assert len(plans) == len(set(patterns.values()))
    for packed in plans:
        for p in packed:
            assert len({patterns[sp.key] for sp in p.spans}) == 1
            # every slab is launched at its bucket's full width
            assert p.shape[0] == p.max_rows
    moved = {k: after[k] - before[k] for k in after
             if k.startswith("rebuild_batch_")}
    assert moved["rebuild_batch_volumes"] == len(SIZES)
    assert moved["rebuild_batch_patterns"] == len(set(patterns.values()))
    assert moved["rebuild_batch_rows"] == sum(
        sp.n for packed in plans for p in packed for sp in p.spans)
    assert 0 < moved["rebuild_batch_rows"] <= \
        moved["rebuild_batch_row_slots"]
    # one slab a dispatch
    assert moved["rebuild_batch_launches"] == sum(len(p) for p in plans)
    # one rebuild_ec_files per volume, on copies, says the same (a
    # batch of one each)
    for key, (base, far, shards, fed) in vols.items():
        one = tmp_path / f"one{key}"
        one.mkdir()
        solo = one / base.name
        for s in range(14):
            if s not in lost[key]:
                shards[s].tofile(ec_files.shard_path(solo, s))
        assert rebuild_mod.rebuild_ec_files(solo, PACKED,
                                            wanted=lost[key]) == lost[key]
        for s in lost[key]:
            assert ec_files.shard_path(solo, s).read_bytes() == \
                ec_files.shard_path(base, s).read_bytes()


def test_a_stream_that_fails_takes_its_volume_and_no_other(tmp_path):
    lost = {key: [1, 6, 11] for key in SIZES}
    vols = lay_out(tmp_path, SIZES, lost, {key: K for key in SIZES})
    feed = FileFeed({key: v[1] for key, v in vols.items()}, cut=3)
    failed = rebuild_mod.rebuild_volumes(repairs_of(vols, lost),
                                         remote=feed,
                                         slab_bytes=3 * K * BLOCK,
                                         durable=True)
    assert {key: str(e) for key, e in failed.items()} == {3: "cut"}
    # nothing of what the run wrote for it; its survivors as they were
    assert not any(ec_files.shard_path(vols[3][0], s).exists()
                   for s in lost[3])
    for key in (1, 2, 4, 5):
        base, _far, shards, _fed = vols[key]
        for s in lost[key]:
            assert np.array_equal(np.fromfile(ec_files.shard_path(base, s),
                                              dtype=np.uint8), shards[s])


def test_a_survivor_read_short_fails_its_volume(tmp_path):
    lost = {key: [1, 6, 11] for key in SIZES}
    vols = lay_out(tmp_path, SIZES, lost, {key: 0 for key in SIZES})
    path = ec_files.shard_path(vols[4][0], 0)
    path.write_bytes(path.read_bytes()[:BLOCK])
    repairs = [rebuild_mod.Repair(
        key, base, PACKED, tuple([s for s in range(14)
                                  if s not in lost[key]][:K]),
        tuple(lost[key]), shards[0].size)
        for key, (base, _far, shards, _fed) in vols.items()]
    failed = rebuild_mod.rebuild_volumes(repairs, durable=True)
    assert list(failed) == [4] and "short read" in str(failed[4])
    assert sorted(ec_files.present_shards(vols[4][0], 14)) == sorted(
        set(range(14)) - set(lost[4]))
    assert all(ec_files.shard_path(vols[2][0], s).exists()
               for s in lost[2])


def test_survivors_of_other_sizes_are_refused_before_the_run(tmp_path):
    lost = {1: [1, 6, 11]}
    vols = lay_out(tmp_path, {1: 2 * BLOCK}, lost, {1: 0})
    path = ec_files.shard_path(vols[1][0], 4)
    path.write_bytes(path.read_bytes() + b"\0")
    with pytest.raises(rebuild_mod.EcRebuildError, match="sizes differ"):
        rebuild_mod.plan_repair(1, vols[1][0], PACKED, lost[1])
    path.unlink()
    ec_files.shard_path(vols[1][0], 5).unlink()
    with pytest.raises(Exception, match="need 10 surviving shards"):
        rebuild_mod.plan_repair(1, vols[1][0], PACKED, lost[1])


# --------------------------------------------------------------------------
# the walk on the rack of four after a server was replaced
# --------------------------------------------------------------------------

VOLS = {1: ROW // 2, 2: ROW + ROW // 3, 3: 2 * ROW + ROW // 5, 4: 3 * ROW}


def sealed(racks, sizes=VOLS):
    rack = racks(sizes)
    for vid in sizes:
        reply, err = rack.run(f"ec.encode -volumeId {vid} -collection {COL}")
        assert err is None, (reply, err)
    return rack


def take(rack, server: int, vid: int, shard_ids=None) -> list:
    """Unmount and delete ``shard_ids`` (all it holds) of ``vid`` on
    ``server`` through its own rpcs; returns them."""
    held = rack.held(vid)[server]
    gone = held if shard_ids is None else list(shard_ids)
    stub = rack.servers[server].peer_stub(rack.servers[server].url)
    stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
        volume_id=vid, shard_ids=gone))
    stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
        volume_id=vid, collection=COL, shard_ids=gone))
    return gone


def files_of(rack, server: int, vid: int) -> list:
    return sorted(p.name for p in rack.dirs[server].glob(f"{COL}_{vid}.*"))


def restored_match_the_reference(rack, server, vid, shard_ids, tmp_path):
    dat = tmp_path / f"sealed{vid}.dat"
    rack.dats[vid].tofile(dat)
    layout = reference.Layout(SCHEME.data_shards, SCHEME.parity_shards,
                              SCHEME.large_block_size,
                              SCHEME.small_block_size)
    sealed_ = reference.Sealed(dat, layout)
    compared, problems = reference.check_shards(
        rack.base(server, vid), sealed_, list(range(sealed_.rows)),
        list(shard_ids))
    assert not problems, problems
    assert compared == len(shard_ids) * sealed_.rows * layout.small


def rpcs_named(name: str) -> int:
    """Spans ``name`` in the trace of the newest ``ec.rebuild``: the
    rpcs it sent, as their servers recorded them."""
    traces = tracing.recent_traces()
    trace_id = next(t for t in reversed(traces)
                    if t["name"] == "shell.ec.rebuild")["trace_id"]
    return sum(1 for t in traces if t["trace_id"] == trace_id
               for s in t["spans"] if s["name"] == name)


def test_the_force_walk_restores_every_volume_by_one_batch_rpc(
        racks, tmp_path, monkeypatch):
    rack = sealed(racks)
    lost = {vid: take(rack, 0, vid) for vid in VOLS}
    # volume 3 loses a shard of a peer too: a second loss pattern
    holder = next(i for i in (1, 2, 3) if len(rack.held(3)[i]) == 4)
    lost[3] = sorted(lost[3] + take(rack, holder, 3, rack.held(3)[holder][:1]))
    assert all(files_of(rack, 0, vid) == [] for vid in VOLS)
    store = rack.servers[0].store
    at_mount, mount = [], store.mount_ec_shards

    def mounted(vid, shard_ids, collection=""):
        totals = flight.totals()
        at_mount.append((vid, totals.get("fsync", (0, 0))[1],
                         totals.get("copy_commit", (0, 0))[1]))
        return mount(vid, shard_ids, collection)
    monkeypatch.setattr(store, "mount_ec_shards", mounted)
    totals = flight.totals()
    fsyncs = totals.get("fsync", (0, 0))[1]
    commits = totals.get("copy_commit", (0, 0))[1]
    before = rack.pipeline_vars()

    reply, err = rack.run(f"ec.rebuild -force -collection {COL}")
    assert err is None, (reply, err)
    for vid in VOLS:
        assert f"ec.rebuild volume {vid}: rebuilt {lost[vid]} on " \
               f"{rack.servers[0].url}" in reply
        assert rack.held(vid)[0] == lost[vid]
        assert files_of(rack, 0, vid) == sorted(
            [f"{COL}_{vid}.ecx", f"{COL}_{vid}.vif"]
            + [f"{COL}_{vid}.ec{s:02d}" for s in lost[vid]])
        restored_match_the_reference(rack, 0, vid, lost[vid], tmp_path)
        assert sorted(s for ids in rack.held(vid) for s in ids) \
            == list(range(TOTAL))
        assert rack.mapped(vid) == {s: [rack.servers[i].url]
                                    for i, ids in enumerate(rack.held(vid))
                                    for s in ids}
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    # one batch rpc, one handler call, one nudge, one pattern run each
    assert rpcs_named("grpc.VolumeEcShardsRebuildBatch") == 1
    assert rpcs_named("grpc.VolumeEcShardsRebuild") == 0
    d = {k: v - before[k] for k, v in rack.pipeline_vars().items()
         if isinstance(v, (int, float))}
    assert d["step_rebuild_calls"] == 1
    assert d["step_rebuild_fetch_calls"] == 1
    assert d["step_rebuild_fetch_index_calls"] == len(VOLS)
    assert d["step_store_mount_calls"] == len(VOLS)
    assert d["rebuild_batch_volumes"] == len(VOLS)
    assert d["rebuild_batch_patterns"] == 2
    assert d["rebuild_batch_launches"] >= 2
    assert d["rebuild_fetch_sources"] == 3
    assert d["rebuild_fetch_files"] == len(VOLS) * (10 + 2)
    assert d["rebuild_fetch_streamed_bytes"] == 10 * sum(
        SCHEME.shard_file_size(rack.dats[vid].size) for vid in VOLS)
    # every restored file and every index file took its barrier before
    # the first mount
    assert len(at_mount) == len(VOLS)
    first_fsyncs, first_commits = min(at_mount, key=lambda m: m[1])[1:]
    assert first_fsyncs - fsyncs == sum(len(g) for g in lost.values())
    assert first_commits - commits == 2 * len(VOLS)


def test_a_fault_in_one_volumes_stream_leaves_nothing_of_it(racks,
                                                            tmp_path):
    rack = sealed(racks)
    lost = {vid: take(rack, 0, vid) for vid in VOLS}
    survivors = {p: p.read_bytes() for d in rack.dirs[1:] for p in d.iterdir()}
    # one chunk per index file (.ecx: the .vif is read into memory and
    # .ecj is absent) in turn, then the streams' slices: a hit among them
    nth = len(VOLS) + 7
    seed = next(s for s in range(2000) if first_hit("error@0.2#1", s) == nth)
    faults.inject("ec.shard_copy", "error@0.2#1", seed=seed)
    reply, err = rack.run(f"ec.rebuild -force -collection {COL}")
    faults.clear()
    assert err is not None and "1 volume(s) failed" in err, (reply, err)
    bad = [vid for vid in VOLS if f"ec.rebuild volume {vid}: failed on "
           f"{rack.servers[0].url}" in reply]
    assert len(bad) == 1, reply
    assert "FaultError" in reply
    assert files_of(rack, 0, bad[0]) == []
    assert (COL, bad[0]) not in rack.servers[0].store.ec_mounts
    for vid in VOLS:
        if vid != bad[0]:
            assert rack.held(vid)[0] == lost[vid]
            restored_match_the_reference(rack, 0, vid, lost[vid], tmp_path)
            assert sorted(rack.servers[0].store.ec_mounts[(COL, vid)]
                          .shard_ids) == lost[vid]
    assert {p: p.read_bytes() for d in rack.dirs[1:]
            for p in d.iterdir()} == survivors
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    # the same walk, sound, repairs what is left, on the server that
    # upstream's rule picks now (the one with most free slots)
    reply, err = rack.run(f"ec.rebuild -force -collection {COL}")
    assert err is None, (reply, err)
    line = next(ln for ln in reply.splitlines()
                if ln.startswith(f"ec.rebuild volume {bad[0]}: "))
    assert line.startswith(f"ec.rebuild volume {bad[0]}: rebuilt "
                           f"{lost[bad[0]]} on "), reply
    there = [vs.url for vs in rack.servers].index(line.rsplit(" ", 1)[1])
    restored_match_the_reference(rack, there, bad[0], lost[bad[0]], tmp_path)
    assert sorted(s for ids in rack.held(bad[0]) for s in ids) \
        == list(range(TOTAL))


def test_healthy_volumes_put_nothing_on_the_rebuilder(racks, monkeypatch):
    """A walk over healthy and damaged volumes on an empty rebuilder:
    a healthy volume's ``.vif`` is read into memory and decides that
    nothing is missing; no file of it is made there."""
    rack = sealed(racks)
    # server 0's shards of volumes 1 and 3 move to a peer (pulled,
    # mounted, deleted here): they stay whole, and server 0 holds
    # nothing of them; it loses its shards of volumes 2 and 4
    for vid in (1, 3):
        ids = rack.held(vid)[0]
        peer = next(i for i in (1, 2, 3) if len(rack.held(vid)[i]) == 3)
        stub = rack.servers[0].peer_stub(rack.servers[peer].url)
        stub.VolumeEcShardsCopy(vpb.VolumeEcShardsCopyRequest(
            volume_id=vid, collection=COL, shard_ids=ids,
            source_data_node=rack.servers[0].url))
        stub.VolumeEcShardsMount(vpb.VolumeEcShardsMountRequest(
            volume_id=vid, collection=COL, shard_ids=ids))
        take(rack, 0, vid)
    lost = {vid: take(rack, 0, vid) for vid in (2, 4)}
    assert all(files_of(rack, 0, vid) == [] for vid in VOLS)
    made = []
    from seaweedfs_tpu.util import durability
    replace = durability.durable_replace

    def noted(src, dst, *a, **kw):
        made.append(Path(dst).name)
        return replace(src, dst, *a, **kw)
    monkeypatch.setattr(durability, "durable_replace", noted)
    before = rack.pipeline_vars()
    reply, err = rack.run(f"ec.rebuild -force -collection {COL}")
    assert err is None, (reply, err)
    for vid in (1, 3):
        assert f"ec.rebuild volume {vid}: all shards present" in reply
        assert files_of(rack, 0, vid) == []
    for vid in (2, 4):
        assert f"ec.rebuild volume {vid}: rebuilt {lost[vid]}" in reply
        assert rack.held(vid)[0] == lost[vid]
    assert sorted(made) == sorted(f"{COL}_{vid}{ext}" for vid in (2, 4)
                                  for ext in (".vif", ".ecx"))
    assert rpcs_named("grpc.VolumeEcShardsRebuildBatch") == 1
    d = {k: v - before[k] for k, v in rack.pipeline_vars().items()
         if isinstance(v, (int, float))}
    # the healthy volumes' .vif came into memory and went no further
    assert d["rebuild_fetch_files"] == 2 * (10 + 2) + 2
    assert d["rebuild_batch_volumes"] == 2
    assert d["step_rebuild_calls"] == 1


def test_volume_id_keeps_its_rpc(racks):
    rack = sealed(racks, {1: ROW, 2: ROW})
    lost = take(rack, 0, 1)
    reply, err = rack.run("ec.rebuild -volumeId 1 -force")
    assert err is None and f"rebuilt {lost}" in reply, (reply, err)
    assert rpcs_named("grpc.VolumeEcShardsRebuild") == 1
    assert rpcs_named("grpc.VolumeEcShardsRebuildBatch") == 0
    # the walk: server 0 rebuilds both (volume 1 whole, its shards held
    # here), as one batch
    lost = take(rack, 0, 2)
    reply, err = rack.run(f"ec.rebuild -force -collection {COL}")
    assert err is None, (reply, err)
    assert "ec.rebuild volume 1: all shards present" in reply
    assert f"ec.rebuild volume 2: rebuilt {lost}" in reply
    assert rpcs_named("grpc.VolumeEcShardsRebuildBatch") == 1
    assert rpcs_named("grpc.VolumeEcShardsRebuild") == 0


def test_the_one_volume_rpc_is_a_batch_of_one_without_the_barrier(racks):
    """``VolumeEcShardsRebuild`` restores through the packed reconstruct
    as a batch of one volume and closes its restored files without the
    ``[storage] fsync`` barrier (the one-volume repair has never had
    one); ``VolumeEcShardsRebuildBatch`` of the same loss passes each
    restored file through it."""
    rack = sealed(racks, {1: ROW})
    stub = rack.servers[0].peer_stub(rack.servers[0].url)

    def fsyncs() -> int:
        return flight.totals().get("fsync", (0, 0))[1]
    for rpc, barriers in (("one", 0), ("batch", 1)):
        lost = take(rack, 0, 1)
        before, fsynced = rack.pipeline_vars(), fsyncs()
        if rpc == "one":
            rebuilt = list(stub.VolumeEcShardsRebuild(
                vpb.VolumeEcShardsRebuildRequest(
                    volume_id=1, collection=COL)).rebuilt_shard_ids)
        else:
            (result,) = stub.VolumeEcShardsRebuildBatch(
                vpb.VolumeEcShardsRebuildBatchRequest(
                    volume_ids=[1], collection=COL)).results
            assert result.error == ""
            rebuilt = list(result.rebuilt_shard_ids)
        assert rebuilt == lost
        assert rack.held(1)[0] == lost
        d = {k: v - before[k] for k, v in rack.pipeline_vars().items()
             if isinstance(v, (int, float))}
        assert d["rebuild_batch_volumes"] == 1
        assert d["step_rebuild_calls"] == 1
        assert fsyncs() - fsynced == barriers * len(lost), rpc
    assert sorted(s for ids in rack.held(1) for s in ids) \
        == list(range(TOTAL))


@pytest.mark.parametrize("rpc", ["one", "batch"])
def test_survivors_that_disagree_with_the_vif_are_refused(racks, rpc):
    """Either rebuild rpc holds the local survivors to the size the
    ``.vif`` says the volume was sealed with: a mismatch restores
    nothing and leaves the volume as it was."""
    import grpc
    rack = sealed(racks, {1: ROW})
    stub = rack.servers[0].peer_stub(rack.servers[0].url)
    base = rack.base(0, 1)
    # one of its shards: the others stay, and so do its index files
    lost = take(rack, 0, 1, rack.held(1)[0][:1])
    kept = rack.held(1)[0]
    info = ec_files.VolumeInfo.load(base)
    info.dat_file_size += ROW
    info.save(base)
    if rpc == "one":
        with pytest.raises(grpc.RpcError) as raised:
            stub.VolumeEcShardsRebuild(vpb.VolumeEcShardsRebuildRequest(
                volume_id=1, collection=COL))
        error = raised.value.details()
    else:
        (result,) = stub.VolumeEcShardsRebuildBatch(
            vpb.VolumeEcShardsRebuildBatchRequest(
                volume_ids=[1], collection=COL)).results
        assert list(result.rebuilt_shard_ids) == []
        error = result.error
    assert "surviving shard sizes differ" in error, error
    assert not any(ec_files.shard_path(base, s).exists() for s in lost)
    assert rack.held(1)[0] == kept


@pytest.mark.parametrize("line", ["ec.rebuild -force",
                                  "ec.rebuild -force -collection c",
                                  "ec.rebuild -collection c -force",
                                  "ec.rebuild -volumeId 3 -force"])
def test_upstreams_maintenance_line_parses(line, monkeypatch):
    from seaweedfs_tpu.shell import cluster_commands as cc
    seen = []

    class Env:
        def collect_ec_nodes(self):
            seen.append(line)
            return []

        def println(self, text):
            seen.append(text)
    cc.CLUSTER_COMMANDS["ec.rebuild"](Env(), line.split()[1:])
    assert seen[0] == line
