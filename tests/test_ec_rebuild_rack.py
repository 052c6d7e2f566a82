"""``ec.rebuild`` on a rack of four after a server was replaced: the
shell's own command, the rebuilder chosen as upstream chooses it.

The rack of ``test_ec_spread.py`` (one master and four volume servers in
this process, RS(10,4), shards 4 + 4 + 3 + 3 after ``ec.encode``), then a
server is emptied through its own rpcs — every shard of the volume
unmounted and deleted, the index files gone with the last one — and
stands for the empty machine that took a dead server's place. The shell
picks it (most free slots); it pulls ``.vif`` / ``.ecx`` to its disk and
reads ten surviving shards off three peers' streams at once, straight
into the pipeline run that restores the lost shards, and ends holding
exactly those. Held against the benchmark's plain reference
(``benchmark/reference.py``: NumPy, imports nothing of the program): the
restored files byte for byte; beside it the survivors untouched, the
counters of what was fetched and what of it never was a file, the three
sources' threads and the one of one source, no survivor's copy on the
rebuilder's disk at any moment of a round, a rebuilder that has some
survivors and fetches the rest, either transport, a fault mid-fetch, a
stream of another length or cut short that leave the server empty, a
volume with too few survivors reported, and ``VolumeEcShardsDelete``
dropping the index files with the last shard only.
"""

import hashlib
import sys
from pathlib import Path

import pytest

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.pipeline import flight
from seaweedfs_tpu.shell.cluster_commands import EcNode, pick_rebuilder
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.util import faults, tracing

from test_ec_spread import (COL, ROW, SCHEME, TOTAL,  # noqa: F401
                            Meeting, first_hit, racks, small_rows,
                            the_plane_of)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
import reference  # noqa: E402  (benchmark/reference.py)

LAYOUT = reference.Layout(SCHEME.data_shards, SCHEME.parity_shards,
                          SCHEME.large_block_size, SCHEME.small_block_size)
INDEX = (ec_files.ecx_path, ec_files.ecj_path, ec_files.vif_path)
SIZE = 2 * ROW + ROW // 5


def sealed_rack(racks):
    rack = racks({1: SIZE})
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    assert err is None, (reply, err)
    return rack


def empty(rack, server: int, shard_ids=None) -> list:
    """Take ``shard_ids`` (all it holds) of volume 1 off ``server``
    through its own rpcs; returns what it held."""
    held = rack.held(1)[server]
    gone = held if shard_ids is None else list(shard_ids)
    stub = rack.servers[server].peer_stub(rack.servers[server].url)
    stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
        volume_id=1, shard_ids=gone))
    stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
        volume_id=1, collection=COL, shard_ids=gone))
    return gone


def files_of(rack, server: int) -> list:
    return sorted(p.name for p in rack.dirs[server].glob(f"{COL}_1.*"))


def digests(rack) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for d in rack.dirs for p in d.glob(f"{COL}_1.*")}


def restored_match_the_reference(rack, server: int, shard_ids,
                                 tmp_path) -> None:
    """Every byte of the restored files against ``reference.py``: data
    shards with the striped ``.dat``, parity on every row."""
    dat = tmp_path / f"sealed{server}.dat"
    rack.dats[1].tofile(dat)
    sealed = reference.Sealed(dat, LAYOUT)
    compared, problems = reference.check_shards(
        rack.base(server, 1), sealed, list(range(sealed.rows)),
        list(shard_ids))
    assert not problems, problems
    assert compared == len(shard_ids) * sealed.rows * LAYOUT.small


def delta(rack, before: dict) -> dict:
    after = rack.pipeline_vars()
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))}


def commits() -> int:
    """Calls of the span ``copy_commit`` (fsync + rename) so far."""
    return flight.totals().get("copy_commit", (0.0, 0))[1]


@pytest.fixture()
def chains(monkeypatch):
    """The fetch threads made from here on, by name."""
    from seaweedfs_tpu.cluster import volume_server as vs_mod
    made, start = [], vs_mod._SurvivorChain.start

    def noted(self):
        made.append(self.name)
        start(self)
    monkeypatch.setattr(vs_mod._SurvivorChain, "start", noted)
    return made


def gather_on(rack, keeper: int, others) -> None:
    """``keeper`` pulls and mounts what ``others`` hold, and they are
    lost: their shards survive on one server."""
    held = rack.held(1)
    stub = rack.servers[0].peer_stub(rack.servers[keeper].url)
    for i in others:
        stub.VolumeEcShardsCopy(vpb.VolumeEcShardsCopyRequest(
            volume_id=1, collection=COL, shard_ids=held[i],
            source_data_node=rack.servers[i].url))
        stub.VolumeEcShardsMount(vpb.VolumeEcShardsMountRequest(
            volume_id=1, collection=COL, shard_ids=held[i]))
        rack.lose(i)


# --------------------------------------------------------------------------
# the rebuilder, as upstream's rebuildEcVolumes picks it
# --------------------------------------------------------------------------

def node(url: str, free: int, shards=()) -> EcNode:
    return EcNode(url=url, data_center="dc1", rack="r1", free_slots=free,
                  shards={1: list(shards)} if shards else {})


@pytest.mark.parametrize("nodes, want", [
    # an empty replacement has the most free slots, and holds nothing
    ([node("a:1", 15, [0, 1, 2, 3]), node("b:1", 15, [4, 5, 6, 7]),
      node("c:1", 15, [8, 9, 10]), node("d:1", 16)], "d:1"),
    # no replacement: equal slots, so the holder of most shards
    ([node("a:1", 15, [0, 1, 2]), node("b:1", 15, [4, 5, 6, 7]),
      node("c:1", 15, [8, 9, 10])], "b:1"),
    # equal slots and shards: the lowest url, whatever the list's order
    ([node("c:1", 15, [8, 9, 10, 11]), node("a:1", 15, [0, 1, 2, 3]),
      node("b:1", 15, [4, 5, 6])], "a:1"),
    # free slots come before shards held
    ([node("a:1", 3, [0, 1, 2, 3]), node("b:1", 9, [4])], "b:1"),
    # one server: itself
    ([node("a:1", 7, range(10))], "a:1"),
], ids=["empty_replacement", "no_replacement_most_shards", "then_by_url",
        "slots_before_shards", "one_server"])
def test_the_rebuilder_is_the_node_with_most_free_slots(nodes, want):
    assert pick_rebuilder(nodes, 1).url == want


# --------------------------------------------------------------------------
# the repair on the rack
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kept", [3, 4], ids=["held_three", "held_four"])
def test_an_emptied_server_fetches_restores_and_holds_the_lost_shards(
        racks, tmp_path, kept):
    rack = sealed_rack(racks)
    held = rack.held(1)
    lost = next(i for i, ids in enumerate(held) if len(ids) == kept)
    gone = empty(rack, lost)
    assert files_of(rack, lost) == []
    assert sorted(rack.mapped(1)) == sorted(set(range(TOTAL)) - set(gone))
    survivors = digests(rack)
    shard_size = SCHEME.shard_file_size(rack.dats[1].size)
    peer = next(i for i in range(4) if i != lost)
    index_bytes = sum(p(rack.base(peer, 1)).stat().st_size
                      for p in INDEX if p(rack.base(peer, 1)).exists())
    before, commits_before = rack.pipeline_vars(), commits()

    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None, (reply, err)
    assert f"rebuilt {gone} on {rack.servers[lost].url}" in reply

    # it ends holding exactly the lost shards and the index files; no
    # fetched copy, no .part; every survivor is the file it was
    assert rack.held(1)[lost] == gone
    assert files_of(rack, lost) == sorted(
        [f"{COL}_1.ecx", f"{COL}_1.vif"]
        + [f"{COL}_1.ec{s:02d}" for s in gone])
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    now = digests(rack)
    assert {p: now[p] for p in survivors} == survivors
    assert [rack.held(1)[i] for i in range(4) if i != lost] \
        == [held[i] for i in range(4) if i != lost]
    restored_match_the_reference(rack, lost, gone, tmp_path)
    for p in (ec_files.ecx_path, ec_files.vif_path):
        assert p(rack.base(lost, 1)).read_bytes() \
            == p(rack.base(peer, 1)).read_bytes()
    # mounted there, and the master says what the disks say
    mount = rack.servers[lost].store.ec_mounts[(COL, 1)]
    assert sorted(mount.shard_ids) == gone
    assert rack.mapped(1) == {s: [rack.servers[i].url]
                              for i, ids in enumerate(rack.held(1))
                              for s in ids}
    # ten survivors and the index files came over, from three sources;
    # the survivors never were files here, and only the index files,
    # which stay, went through fsync + rename
    d = delta(rack, before)
    assert d["rebuild_fetch_bytes"] == 10 * shard_size + index_bytes
    assert d["copy_file_bytes"] == d["copy_recv_bytes"] \
        == d["rebuild_fetch_bytes"]
    assert d["rebuild_fetch_streamed_bytes"] == 10 * shard_size
    assert d["copy_recv_http_bytes"] == d["copy_recv_bytes"]
    assert d["rebuild_fetch_files"] == 10 + 2
    assert d["rebuild_fetch_sources"] == 3
    assert d["step_rebuild_fetch_calls"] == 1
    assert d["step_rebuild_fetch_index_calls"] == 1
    assert d["step_rebuild_fetch_source_calls"] == 3
    # a chunk a row here: a stream's slice of each of its rows, an
    # index file's one read or write
    assert d["copy_recv_chunks"] == 10 * (
        shard_size // SCHEME.small_block_size) + 2
    # the fetch's wall holds the longest stream, and ends before the
    # handler does
    assert 0 < d["step_rebuild_fetch_seconds"] < d["step_rebuild_seconds"]
    assert d["copy_recv_wait_seconds"] <= d["copy_recv_seconds"] \
        <= 12 * d["step_rebuild_fetch_seconds"]
    assert commits() - commits_before == 2
    mc = MasterClient(rack.master.url)
    try:
        for fid, data in rack.needles[1][:6]:
            assert operation.download(mc, fid, COL) == data
    finally:
        mc.close()


def test_the_fetch_is_one_trace_beneath_the_rebuild_rpc(racks):
    """``step_rebuild_fetch`` holds the index fetch and the three
    source chains, which continue the rpc's trace on their threads;
    every stream opened on a peer's HTTP plane hangs beneath its
    chain."""
    rack = sealed_rack(racks)
    empty(rack, 0)
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None, (reply, err)
    trace_id = next(t for t in reversed(tracing.recent_traces())
                    if t["name"] == "shell.ec.rebuild")["trace_id"]
    spans = {s["span_id"]: s for t in tracing.recent_traces()
             if t["trace_id"] == trace_id for s in t["spans"]}

    def named(name):
        return [s for s in spans.values() if s["name"] == name]

    def ancestors(span):
        while span["parent_id"] in spans:
            span = spans[span["parent_id"]]
            yield span["name"]
    (rpc,), (fetch,) = named("grpc.VolumeEcShardsRebuild"), \
        named("step_rebuild_fetch")
    assert "grpc.VolumeEcShardsRebuild" in ancestors(fetch)
    (index,), chains = named("step_rebuild_fetch_index"), \
        named("step_rebuild_fetch_source")
    assert len(chains) == 3
    for span in [index] + chains:
        assert span["parent_id"] == fetch["span_id"]
        assert rpc["name"] in ancestors(span)
    pulls = [s for s in named("volume.GET")
             if "step_rebuild_fetch_source" in ancestors(s)]
    assert len(pulls) == 10
    # the restore is the handler's, beside the fetch and not beneath it
    (restore,) = named("ec.rebuild_batch")
    assert "step_rebuild_fetch" not in ancestors(restore)
    assert "grpc.VolumeEcShardsRebuild" in ancestors(restore)


def test_three_sources_are_pulled_at_once(racks, chains):
    rack = sealed_rack(racks)
    gone = empty(rack, 0)
    # the index files are no streams: the three chains' first streams
    # meet, each on its chain's thread
    rack.servers[0].fetch_streams = Meeting(
        3, "rebuild_fetch_shared_seconds")
    before = rack.pipeline_vars()
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None and f"rebuilt {gone}" in reply, (reply, err)
    d = delta(rack, before)
    assert d["rebuild_fetch_sources"] == 3
    assert sorted(chains) == [f"rebuild-fetch-{n}" for n in range(3)]
    # ten streams open together, of the twelve that were opened
    assert d["rebuild_fetch_shared_seconds"] > 0
    assert d["rebuild_fetch_shared_seconds"] <= d["copy_recv_seconds"]


def test_one_source_has_one_chain_and_no_pool_of_them(racks, chains):
    """Everything that survives lies on one peer (it pulled the other
    holders' shards before they were lost): one chain holds the ten
    streams, on the one thread the reader needs beside itself, and
    reads them in the slab's order."""
    rack = sealed_rack(racks)
    held = rack.held(1)
    keeper = next(i for i in (1, 2, 3) if len(held[i]) == 4)
    gather_on(rack, keeper, [i for i in (1, 2, 3) if i != keeper])
    gone = empty(rack, 0)
    before = rack.pipeline_vars()
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None, (reply, err)
    assert f"rebuilt {gone} on {rack.servers[0].url}" in reply
    d = delta(rack, before)
    assert d["rebuild_fetch_sources"] == 1
    assert d["step_rebuild_fetch_source_calls"] == 1
    assert d["rebuild_fetch_files"] == 10 + 2
    assert chains == ["rebuild-fetch-0"]
    # its ten streams are open together all the same
    assert d["rebuild_fetch_shared_seconds"] > 0
    assert rack.held(1)[0] == gone


def test_a_rebuilder_with_its_survivors_local_fetches_nothing(racks,
                                                              chains):
    """What every one-server cell does: nothing to pull, so no feed, no
    thread, and every counter of the fetch stays where it was."""
    rack = sealed_rack(racks)
    gather_on(rack, 0, (1, 2, 3))
    gone = empty(rack, 0, [1, 6, 11])
    before = rack.pipeline_vars()
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None and f"rebuilt {gone}" in reply, (reply, err)
    d = delta(rack, before)
    for key in ("rebuild_fetch_bytes", "rebuild_fetch_files",
                "rebuild_fetch_sources", "rebuild_fetch_shared_seconds",
                "rebuild_fetch_streamed_bytes",
                "step_rebuild_fetch_index_calls",
                "step_rebuild_fetch_source_calls", "copy_recv_bytes",
                "copy_recv_chunks", "copy_recv_seconds"):
        assert d[key] == 0, key
    assert d["step_rebuild_fetch_calls"] == 1
    assert chains == []


def test_a_rebuilder_with_some_survivors_fetches_the_rest(racks, tmp_path,
                                                          chains):
    """A peer's four shards are lost and the sealing server, which
    holds three, is told to rebuild: its own three are read from their
    files, seven come off the two other peers' streams, and the four
    restored are the reference's, as with every survivor local."""
    rack = sealed_rack(racks)
    held = rack.held(1)
    dead = next(i for i in (1, 2, 3) if len(held[i]) == 4)
    gone = empty(rack, dead)
    shard_size = SCHEME.shard_file_size(rack.dats[1].size)
    survivors = digests(rack)
    before, commits_before = rack.pipeline_vars(), commits()
    stub = rack.servers[0].peer_stub(rack.servers[0].url)
    resp = stub.VolumeEcShardsRebuild(vpb.VolumeEcShardsRebuildRequest(
        volume_id=1, collection=COL))
    assert list(resp.rebuilt_shard_ids) == gone
    assert rack.held(1)[0] == sorted(held[0] + gone)
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    restored_match_the_reference(rack, 0, gone, tmp_path)
    now = digests(rack)
    assert {p: now[p] for p in survivors} == survivors
    # the lowest ids first until ten are at hand: seven off two peers
    d = delta(rack, before)
    assert d["rebuild_fetch_files"] == 7
    assert d["rebuild_fetch_bytes"] == d["rebuild_fetch_streamed_bytes"] \
        == d["copy_recv_bytes"] == 7 * shard_size
    assert d["rebuild_fetch_sources"] == 2 and len(chains) == 2
    assert d["step_rebuild_fetch_index_calls"] == 0
    assert commits() == commits_before


def test_under_tls_the_survivors_come_as_copyfile_streams(racks, tmp_path):
    """The gRPC plane under mutual TLS: every stream is a ``CopyFile``
    stream, whose messages are copied into the slices; it says no
    length, so the run is sized by the ``.vif`` fetched."""
    with the_plane_of("grpc", tmp_path):
        rack = sealed_rack(racks)
        gone = empty(rack, 0)
        shard_size = SCHEME.shard_file_size(rack.dats[1].size)
        before = rack.pipeline_vars()
        reply, err = rack.run("ec.rebuild -volumeId 1")
        assert err is None and f"rebuilt {gone}" in reply, (reply, err)
        d = delta(rack, before)
        restored_match_the_reference(rack, 0, gone, tmp_path)
        assert files_of(rack, 0) == sorted(
            [f"{COL}_1.ecx", f"{COL}_1.vif"]
            + [f"{COL}_1.ec{s:02d}" for s in gone])
    assert d["rebuild_fetch_streamed_bytes"] == 10 * shard_size
    assert d["rebuild_fetch_files"] == 10 + 2
    assert d["copy_recv_http_bytes"] == d["copy_file_sendfile_bytes"] == 0
    assert d["copy_file_bytes"] == d["copy_recv_bytes"] \
        == d["rebuild_fetch_bytes"]


def test_no_survivor_is_a_file_on_the_rebuilder_at_any_point_of_a_round(
        racks, monkeypatch):
    """The replacement's directory listed from the fault point, behind
    every chunk landed (a row of each stream, and ``.ecx``) and from the
    positioned writes of the restore: the index files, their ``.part`` while they come, the
    shards being restored, and never a survivor's shard or ``.part``."""
    rack = sealed_rack(racks)
    gone = empty(rack, 0)
    from seaweedfs_tpu.pipeline import writeback
    seen, looks = set(), []
    check, submit = faults.check, writeback.WriterPool.submit

    def look(where):
        looks.append(where)
        seen.update(files_of(rack, 0))

    def checked(name, *a, **kw):
        if name == "ec.shard_copy":
            look("chunk")
        return check(name, *a, **kw)

    def submitted(self, *a, **kw):
        look("write")
        return submit(self, *a, **kw)
    monkeypatch.setattr(faults, "check", checked)
    monkeypatch.setattr(writeback.WriterPool, "submit", submitted)
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None and f"rebuilt {gone}" in reply, (reply, err)
    rows = SCHEME.shard_file_size(rack.dats[1].size) \
        // SCHEME.small_block_size
    assert looks.count("chunk") == 10 * rows + 1 and "write" in looks
    restored = {f"{COL}_1.ec{s:02d}" for s in gone}
    index = {f"{COL}_1{ext}" for ext in (".vif", ".ecx")}
    assert restored <= seen and index <= seen
    assert seen <= restored | index | {n + ".part" for n in index}


# --------------------------------------------------------------------------
# all or nothing
# --------------------------------------------------------------------------

def left_empty_and_repairable(rack, survivors, mapped) -> None:
    """Nothing of the volume on the replacement, every survivor and
    the master's map as they were; and the same command, sound, repairs
    it."""
    assert files_of(rack, 0) == []
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    assert (COL, 1) not in rack.servers[0].store.ec_mounts
    assert digests(rack) == survivors
    assert rack.mapped(1) == mapped
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None and "rebuilt" in reply, (reply, err)
    assert sorted(s for ids in rack.held(1) for s in ids) \
        == list(range(TOTAL))


@pytest.mark.parametrize("nth", [1, 6], ids=["in_the_index_files",
                                             "among_the_survivors"])
def test_a_fault_mid_fetch_leaves_the_server_empty(racks, nth):
    """A chunk a row here: .ecx (the .vif is read into memory, and has
    no fault point), then the ten survivors' rows over three chains.
    The ``nth`` chunk landed fails its file or its stream; the command fails, and nothing of the volume is left on
    the replacement: no index file, no restored shard, no file of any
    other kind."""
    rack = sealed_rack(racks)
    empty(rack, 0)
    survivors = digests(rack)
    mapped = rack.mapped(1)
    spec = "error@0.2#1"
    seed = next(s for s in range(1000) if first_hit(spec, s) == nth)
    commits_before = commits()
    faults.inject("ec.shard_copy", spec, seed=seed)
    reply, err = rack.run("ec.rebuild -volumeId 1")
    faults.clear()
    assert err is not None and "1 volume(s) failed" in err
    assert f"failed on {rack.servers[0].url}" in reply
    assert list(rack.dirs[0].iterdir()) == []
    # only index files ever take the barrier, fault or none
    assert commits() - commits_before <= 2
    left_empty_and_repairable(rack, survivors, mapped)


@pytest.mark.parametrize("how, said", [
    ("longer", "surviving shard sizes differ"),
    ("shorter", "surviving shard sizes differ"),
    ("cut", "short of the survivors' size"),
])
def test_a_stream_of_another_length_fails_the_call(racks, monkeypatch,
                                                   how, said):
    """A survivor whose holder announces another length than the others
    fails the call before a chunk is read; one that ends before the
    length it announced fails it in the run. Either way the replacement
    is left empty."""
    rack = sealed_rack(racks)
    empty(rack, 0)
    holder = next(i for i in (1, 2, 3) if rack.held(1)[i])
    path = ec_files.shard_path(rack.base(holder, 1),
                               rack.held(1)[holder][0])
    whole = path.read_bytes()
    from seaweedfs_tpu.cluster import volume_server as vs_mod
    if how == "cut":
        real = vs_mod._open_body

        def open_body(vs, url, vid, col, ext):
            body = real(vs, url, vid, col, ext)
            if ext == path.suffix:
                readinto, took = body.readinto, []

                def cut(view):
                    # half of the file, then the peer is gone
                    if sum(took) >= len(whole) // 2:
                        return 0
                    took.append(readinto(view[:len(whole) // 2]))
                    return took[-1]
                body.readinto = cut
            return body
        monkeypatch.setattr(vs_mod, "_open_body", open_body)
    else:
        path.write_bytes(whole + b"\0" if how == "longer" else whole[:-1])
    survivors = digests(rack)
    mapped = rack.mapped(1)
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is not None and "1 volume(s) failed" in err
    assert said in reply + err, (reply, err)
    if how == "cut":
        monkeypatch.undo()
    else:
        assert digests(rack) == survivors
        path.write_bytes(whole)
        survivors = digests(rack)
    left_empty_and_repairable(rack, survivors, mapped)


def test_a_survivor_is_asked_of_a_second_holder_when_the_first_refuses(
        racks, monkeypatch):
    """A shard mounted on two servers: its stream is opened on the
    second when the first refuses at the open."""
    rack = sealed_rack(racks)
    held = rack.held(1)
    a, b = (i for i in (1, 2, 3) if len(held[i]) == 4)
    stub = rack.servers[0].peer_stub(rack.servers[b].url)
    stub.VolumeEcShardsCopy(vpb.VolumeEcShardsCopyRequest(
        volume_id=1, collection=COL, shard_ids=held[a][:1],
        source_data_node=rack.servers[a].url))
    stub.VolumeEcShardsMount(vpb.VolumeEcShardsMountRequest(
        volume_id=1, collection=COL, shard_ids=held[a][:1]))
    twice = held[a][0]
    assert len(rack.mapped(1)[twice]) == 2
    gone = empty(rack, 0)
    from seaweedfs_tpu.cluster import volume_server as vs_mod
    real, asked = vs_mod._open_body, []

    def open_body(vs, src_url, vid, col, ext):
        if ext == ec_files.shard_ext(twice):
            asked.append(src_url)
            if len(asked) == 1:
                raise OSError("the first holder is not answering")
        return real(vs, src_url, vid, col, ext)
    monkeypatch.setattr(vs_mod, "_open_body", open_body)
    before = rack.pipeline_vars()
    reply, err = rack.run("ec.rebuild -volumeId 1")
    assert err is None and f"rebuilt {gone}" in reply, (reply, err)
    assert sorted(asked) == sorted(rack.mapped(1)[twice])
    assert rack.held(1)[0] == gone
    # the refusal moved no survivor to another chain and counted none
    d = delta(rack, before)
    assert d["rebuild_fetch_files"] == 10 + 2
    assert d["rebuild_fetch_sources"] == 3


def test_fewer_than_k_survivors_is_reported_and_the_walk_goes_on(racks):
    rack = racks({1: SIZE, 2: ROW})
    for vid in (1, 2):
        reply, err = rack.run(f"ec.encode -volumeId {vid} "
                              f"-collection {COL}")
        assert err is None, (reply, err)
    # volume 1 loses a server's share and two shards more: nine survive
    gone = empty(rack, 0)
    other = next(i for i in (1, 2, 3) if len(rack.held(1)[i]) == 4)
    taken = rack.held(1)[other][:5 - len(gone)]
    stub = rack.servers[0].peer_stub(rack.servers[other].url)
    stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
        volume_id=1, shard_ids=taken))
    stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
        volume_id=1, collection=COL, shard_ids=taken))
    # volume 2 loses one shard
    holder = next(i for i in range(4) if rack.held(2)[i])
    lost2 = rack.held(2)[holder][:1]
    stub = rack.servers[0].peer_stub(rack.servers[holder].url)
    stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
        volume_id=2, shard_ids=lost2))
    stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
        volume_id=2, collection=COL, shard_ids=lost2))
    before = {vid: rack.held(vid) for vid in (1, 2)}
    reply, err = rack.run("ec.rebuild")
    assert err is None, (reply, err)
    assert "ec.rebuild volume 1: unrepairable with 9 shards" in reply
    assert f"ec.rebuild volume 2: rebuilt {lost2}" in reply
    # nothing of volume 1 moved, and its rebuilder is as empty as it was
    assert rack.held(1) == before[1]
    assert not list(rack.dirs[0].glob(f"{COL}_1.*"))
    assert sorted(s for ids in rack.held(2) for s in ids) \
        == list(range(TOTAL))


# --------------------------------------------------------------------------
# VolumeEcShardsDelete and the index files
# --------------------------------------------------------------------------

def test_the_index_files_go_with_a_servers_last_shard_and_not_before(
        racks):
    rack = sealed_rack(racks)
    server = next(i for i in range(4) if len(rack.held(1)[i]) == 3)
    first, *rest = rack.held(1)[server]
    empty(rack, server, [first])
    assert rack.held(1)[server] == rest
    for p in (ec_files.ecx_path, ec_files.vif_path):
        assert p(rack.base(server, 1)).exists()
    empty(rack, server, rest)
    assert files_of(rack, server) == []
    assert rack.servers[server].store.ec_base(1, COL) is None
    # the other holders keep theirs
    for i in range(4):
        if i != server:
            assert ec_files.ecx_path(rack.base(i, 1)).exists()
