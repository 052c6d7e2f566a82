"""EC artifact files: .ec00-.ec13 shard names, .ecj delete journal, .vif.

Mirrors weed/storage/erasure_coding/ (ec_encoder.go ToExt, ec_volume.go,
ec_volume_delete.go, ec_volume_info.go; SURVEY.md §2, §5):

* shard files ``<base>.ec00`` .. ``.ec13`` — raw striped blocks;
* ``.ecj`` — append-only journal of deleted needle ids (8-byte big-endian
  each), replayed over the .ecx when decoding back to a normal volume;
* ``.vif`` — VolumeInfo as JSON (the reference serializes the VolumeInfo
  protobuf with jsonpb; the field names here match its JSON form).
"""

from __future__ import annotations

import json
import struct
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterator


def shard_ext(shard_id: int) -> str:
    """ec_encoder.go ToExt: ".ec00" ... ".ec13" (always two digits)."""
    if not 0 <= shard_id <= 99:
        raise ValueError(f"shard id {shard_id} out of range")
    return f".ec{shard_id:02d}"


def shard_path(base: str | Path, shard_id: int) -> Path:
    return Path(str(base) + shard_ext(shard_id))


def ecx_path(base: str | Path) -> Path:
    return Path(str(base) + ".ecx")


def ecj_path(base: str | Path) -> Path:
    return Path(str(base) + ".ecj")


def vif_path(base: str | Path) -> Path:
    return Path(str(base) + ".vif")


# -- .ecj delete journal ----------------------------------------------------


def ecj_append(base: str | Path, needle_id: int) -> None:
    """Record a post-seal delete (ec_volume_delete.go
    markNeedleDeleted writes the 8-byte needle id)."""
    with open(ecj_path(base), "ab") as f:
        f.write(struct.pack(">Q", needle_id))


def ecj_read(base: str | Path) -> list[int]:
    p = ecj_path(base)
    if not p.exists():
        return []
    blob = p.read_bytes()
    if len(blob) % 8:
        raise ValueError(f"{p} length {len(blob)} not a multiple of 8")
    return [struct.unpack_from(">Q", blob, o)[0]
            for o in range(0, len(blob), 8)]


def ecj_deleted_set(base: str | Path) -> set[int]:
    return set(ecj_read(base))


# -- .vif volume info -------------------------------------------------------


@dataclass
class VolumeInfo:
    """Subset of volume_server_pb.VolumeInfo the EC path uses; serialized
    as JSON like the reference's jsonpb-saved .vif."""

    version: int = 3
    replication: str = ""
    ttl: str = ""
    dat_file_size: int = 0  # true .dat size (pre-padding), for decode
    # RS geometry used at encode time (BASELINE config 4 parametrization);
    # 0 means the RS(10,4) default.
    data_shards: int = 0
    parity_shards: int = 0

    def save(self, base: str | Path) -> None:
        doc = {"version": self.version}
        if self.replication:
            doc["replication"] = self.replication
        if self.ttl:
            doc["ttl"] = self.ttl
        if self.dat_file_size:
            doc["datFileSize"] = self.dat_file_size
        if self.data_shards:
            doc["dataShards"] = self.data_shards
        if self.parity_shards:
            doc["parityShards"] = self.parity_shards
        vif_path(base).write_text(json.dumps(doc))

    @classmethod
    def load(cls, base: str | Path) -> "VolumeInfo":
        p = vif_path(base)
        if not p.exists():
            return cls()
        return cls.parse(p.read_bytes())

    @classmethod
    def parse(cls, raw: bytes | str) -> "VolumeInfo":
        """A ``.vif``'s bytes, wherever they were read from."""
        doc = json.loads(raw)
        return cls(version=int(doc.get("version", 3)),
                   replication=doc.get("replication", ""),
                   ttl=doc.get("ttl", ""),
                   dat_file_size=int(doc.get("datFileSize", 0)),
                   data_shards=int(doc.get("dataShards", 0)),
                   parity_shards=int(doc.get("parityShards", 0)))


# -- shard presence ---------------------------------------------------------


def present_shards(base: str | Path, total: int = 14) -> list[int]:
    return [i for i in range(total) if shard_path(base, i).exists()]


class ShardBits:
    """Bitmask of mounted shards, as sent in heartbeats
    (ec_volume_info.go ShardBits)."""

    def __init__(self, bits: int = 0):
        self.bits = bits

    @classmethod
    def from_ids(cls, ids) -> "ShardBits":
        b = 0
        for i in ids:
            b |= 1 << i
        return cls(b)

    def add(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits | (1 << shard_id))

    def remove(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits & ~(1 << shard_id))

    def has(self, shard_id: int) -> bool:
        return bool(self.bits >> shard_id & 1)

    def ids(self) -> list[int]:
        return [i for i in range(self.bits.bit_length())
                if self.bits >> i & 1]

    def count(self) -> int:
        return bin(self.bits).count("1")

    def __eq__(self, other) -> bool:
        return isinstance(other, ShardBits) and self.bits == other.bits

    def __repr__(self) -> str:
        return f"ShardBits({self.ids()})"
