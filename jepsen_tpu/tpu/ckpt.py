"""Crash-consistent checkpoint store for incremental re-checking.

ROADMAP item 3's checkpoint-and-extend layer (JASS-style crash-
consistent checkpoints, arXiv:2301.11511): a checker that already
certified a prefix of a history should never pay for that prefix
again — not after a SIGKILL, not when a run-dir grows, not when a
fleet stream resumes. This module is the durable store those resumes
trust:

  record    one schema-validated JSON dict per checkpoint file, keyed
            by a history-prefix digest. Three kinds:
              stream-wgl   a streaming run's frontier: entries
                           certified, reachable-state mask, raw-op
                           prefix digest (fleet.scheduler.StreamingRun)
              wgl-extend   the segmented extend-check's frontier: the
                           stride-stable cut layout, per-cut entry
                           digests, and every resolved
                           (segment, state) -> reach mask
                           (wgl.analysis_extend)
              elle         a committed-txn graph summary: per-key
                           version orders + the SCC condensation
                           frontier (elle.StreamingElle)
  framing   CKPT_MAGIC + <len, crc32> + payload — the jlog discipline.
            Writes go to a tmp file (fsync'd) then os.replace, so a
            reader sees old-or-new, never torn. A torn/truncated/
            stale file (chaos can seed all three) is DETECTED AND
            DISCARDED, never trusted: bad magic / short frame / CRC
            mismatch / schema violation all read as None with a
            counted telemetry event, and the caller falls back to a
            full re-check.
  digests   sha256 over the canonical store codec bytes of the
            history prefix (ops_digest) or over the encoded entry
            prefix (entry_digest_chain). A digest mismatch means the
            checkpointed prefix is NOT a prefix of the history at
            hand — `ckpt.stale` is counted and the checkpoint is
            ignored. Never a wrong verdict, only a slower one.

Durability faults (ENOSPC/EIO — chaos injects them via
set_fault_hook) surface as OSError from write(); try_write() absorbs
them into a False + `ckpt.write-error` count so serving paths shed
instead of crashing. See doc/robustness.md, "Checkpoint-and-extend".
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import threading
import zlib
from pathlib import Path

from .. import telemetry, util

CKPT_MAGIC = b"JTPUCKP1"
_HDR = struct.Struct("<II")
VERSION = 1

KINDS = ("stream-wgl", "wgl-extend", "elle")

# chaos hook: called as hook(path, data) before the tmp write; may
# raise OSError (ENOSPC/EIO) or return mutated bytes (torn/stale
# seeding). Installed/cleared under _hook_lock (chaos.DurabilityChaos).
_fault_hook = None
_hook_lock = threading.Lock()


def set_fault_hook(hook) -> None:
    """Installs (or, with None, clears) the write-path fault hook —
    the chaos rig's injection point for ENOSPC/EIO and seeded
    torn/stale checkpoint bytes."""
    global _fault_hook
    with _hook_lock:
        _fault_hook = hook


# ---------------------------------------------------------------------------
# Digests
# ---------------------------------------------------------------------------

def ops_digest(ops, n: int | None = None) -> str:
    """sha256 hex over the canonical store-codec bytes of ops[:n] —
    the history-prefix key. Stable across live streaming, WAL replay,
    and history.jlog recovery: all three hand the same Op objects to
    the same codec."""
    from ..store import format as fmt

    h = hashlib.sha256()
    take = len(ops) if n is None else min(n, len(ops))
    for i in range(take):
        h.update(fmt.encode_op(ops[i]))
        h.update(b"\n")
    return h.hexdigest()


def entry_digest_chain(enc, cuts) -> list[str]:
    """One sha256 hex per cut: digest i covers the ENCODED entries
    [0, cuts[i]). Entries before a valid cut are fully determined by
    the history prefix (every one completed before later ops invoked),
    so the chain is prefix-stable under history growth — the property
    wgl-extend records key on."""
    h = hashlib.sha256()
    out: list[str] = []
    pos = 0
    for c in cuts:
        while pos < c:
            op = enc.entry_ops[pos]
            line = json.dumps(
                [int(getattr(op, "index", -1)), str(op.process),
                 str(op.f), _jsonable(op.value),
                 bool(enc.crashed[pos])],
                separators=(",", ":"), sort_keys=True)
            h.update(line.encode())
            h.update(b"\n")
            pos += 1
        out.append(h.hexdigest())
    return out


def _jsonable(v):
    from ..store import format as fmt

    return fmt.jsonable(v)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def _bad(msg: str) -> None:
    raise ValueError(f"checkpoint record: {msg}")


def validate_record(rec) -> None:
    """Raises ValueError unless `rec` is a schema-valid checkpoint
    record. A record that fails here is never trusted — the reader
    treats it exactly like a torn file."""
    if not isinstance(rec, dict):
        _bad(f"not a dict: {type(rec).__name__}")
    if rec.get("v") != VERSION:
        _bad(f"bad version {rec.get('v')!r}")
    kind = rec.get("kind")
    if kind not in KINDS:
        _bad(f"unknown kind {kind!r}")
    dig = rec.get("digest")
    if not (isinstance(dig, str) and len(dig) == 64):
        _bad(f"bad digest {dig!r}")
    n_ops = rec.get("n_ops")
    if not isinstance(n_ops, int) or isinstance(n_ops, bool) \
            or n_ops < 0:
        _bad(f"bad n_ops {n_ops!r}")
    if kind == "stream-wgl":
        for k in ("checked", "mask"):
            v = rec.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                _bad(f"bad {k} {v!r}")
        if not isinstance(rec.get("model"), str):
            _bad("bad model")
    elif kind == "wgl-extend":
        for k in ("stride", "model_fp"):
            v = rec.get(k)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                _bad(f"bad {k} {v!r}")
        cuts = rec.get("cuts")
        if not (isinstance(cuts, list) and len(cuts) >= 2
                and all(isinstance(c, int) and not isinstance(c, bool)
                        and c >= 0 for c in cuts)
                and all(a <= b for a, b in zip(cuts, cuts[1:]))):
            _bad(f"bad cuts {cuts!r}")
        digs = rec.get("digests")
        if not (isinstance(digs, list) and len(digs) == len(cuts)
                and all(isinstance(d, str) and len(d) == 64
                        for d in digs)):
            _bad("bad digests")
        states = rec.get("states")
        if not (isinstance(states, list) and 0 < len(states) <= 32
                and all(isinstance(s, str) for s in states)):
            _bad("bad states")
        masks = rec.get("masks")
        if not isinstance(masks, dict):
            _bad("bad masks")
        for key, m in masks.items():
            parts = str(key).split(":")
            if len(parts) != 2 or not all(p.isdigit() for p in parts):
                _bad(f"bad mask key {key!r}")
            if not isinstance(m, int) or isinstance(m, bool) or m < 0:
                _bad(f"bad mask {m!r}")
    elif kind == "elle":
        if not isinstance(rec.get("family"), str):
            _bad("bad family")
        n = rec.get("n_closed")
        if not isinstance(n, int) or isinstance(n, bool) or n < 0:
            _bad(f"bad n_closed {n!r}")
        versions = rec.get("versions")
        if not isinstance(versions, dict) or not all(
                isinstance(vs, list) for vs in versions.values()):
            _bad("bad versions")
        frontier = rec.get("frontier")
        if not isinstance(frontier, dict):
            _bad("bad frontier")


# ---------------------------------------------------------------------------
# Paths
# ---------------------------------------------------------------------------

def fleet_path(base, tenant: str, run: str) -> Path:
    """The fleet's per-(tenant, run) stream checkpoint file."""
    from ..fleet import wal as fwal

    assert fwal.safe_name(tenant) and fwal.safe_name(run), (tenant,
                                                            run)
    return Path(base) / "ckpt" / tenant / f"{run}.ckpt"


def run_dir_path(d, name: str) -> Path:
    """A stored run-dir's checkpoint file (analyze --resume reuse)."""
    return Path(d) / "ckpt" / f"{name}.ckpt"


# ---------------------------------------------------------------------------
# Atomic write / validated read
# ---------------------------------------------------------------------------

def write(path, rec: dict) -> Path:
    """Schema-validates and atomically writes one checkpoint record:
    CRC-framed payload to a tmp file, fsync, os.replace. Raises
    OSError on durability faults (ENOSPC/EIO, injected or real) after
    counting `ckpt.write-error` — callers on serving paths use
    try_write() and shed instead."""
    validate_record(rec)
    p = Path(path)
    payload = json.dumps(rec, separators=(",", ":"),
                         sort_keys=True).encode()
    data = (CKPT_MAGIC
            + _HDR.pack(len(payload), zlib.crc32(payload)) + payload)
    with _hook_lock:
        hook = _fault_hook
    try:
        if hook is not None:
            data = hook(p, data)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = p.with_suffix(".tmp")
        fd = os.open(tmp, os.O_CREAT | os.O_TRUNC | os.O_WRONLY)
        try:
            util.write_all(fd, data)
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, p)
    except OSError:
        telemetry.count("ckpt.write-error")
        raise
    telemetry.count("ckpt.saved")
    return p


def try_write(path, rec: dict) -> bool:
    """write(), with durability faults absorbed: False means the
    checkpoint did NOT land (the stream keeps running from its
    previous one — degraded, never wrong)."""
    try:
        write(path, rec)
        return True
    except OSError:
        return False


def read(path) -> dict | None:
    """The record, or None for missing/torn/truncated/corrupt/
    schema-invalid files — each counted, none trusted."""
    p = Path(path)
    try:
        buf = p.read_bytes()
    except OSError:
        return None
    if buf[:len(CKPT_MAGIC)] != CKPT_MAGIC:
        telemetry.count("ckpt.torn")
        return None
    pos = len(CKPT_MAGIC)
    if len(buf) < pos + _HDR.size:
        telemetry.count("ckpt.torn")
        return None
    n, crc = _HDR.unpack(buf[pos:pos + _HDR.size])
    payload = buf[pos + _HDR.size:pos + _HDR.size + n]
    if len(payload) < n or zlib.crc32(payload) != crc:
        telemetry.count("ckpt.torn")
        return None
    try:
        rec = json.loads(payload)
        validate_record(rec)
    except ValueError:
        telemetry.count("ckpt.invalid")
        return None
    return rec


def load(path, kind: str, digest: str | None = None,
         n_ops: int | None = None) -> dict | None:
    """read() + kind/digest screening. A digest (or op-count) mismatch
    means the checkpoint describes a DIFFERENT history prefix: count
    `ckpt.stale` and fall back to the full check — stale checkpoints
    cost time, never correctness."""
    rec = read(path)
    if rec is None or rec.get("kind") != kind:
        return None
    if n_ops is not None and rec.get("n_ops", 0) > n_ops:
        telemetry.count("ckpt.stale")
        return None
    if digest is not None and rec.get("digest") != digest:
        telemetry.count("ckpt.stale")
        return None
    return rec
