"""Native (C) components and their loader.

The reference keeps native code on its store hot path
(jepsen/src/jepsen/store/FressianReader.java — a patched binary
deserializer — and FileOffsetOutputStream.java); here the analog is a
small C codec for the CRC-framed history log, compiled on first use
with the system compiler and loaded over ctypes. Everything has a pure
Python fallback, so a missing toolchain only costs speed.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from pathlib import Path

logger = logging.getLogger(__name__)

_SRCS = (Path(__file__).with_name("jlog.c"),
         Path(__file__).with_name("order.c"))
_LOCK = threading.Lock()
_lib = None
_tried = False


def _build_dir() -> Path:
    d = Path(os.environ.get("JEPSEN_TPU_NATIVE_DIR",
                            Path.home() / ".cache" / "jepsen_tpu"))
    d.mkdir(parents=True, exist_ok=True)
    return d


def _compile_src(srcs, stem: str, extra_args=()) -> Path | None:
    """Compile C sources to a content-hash-named .so in the build
    cache. Cache keyed on source CONTENT: mtime comparisons break when
    a stale .so outlives a package upgrade (archive mtimes can sort
    older), and loading one without the newer symbols would brick the
    whole codec for the process."""
    import hashlib

    digest = hashlib.sha256()
    for s in srcs:
        digest.update(s.read_bytes())
    build = _build_dir()
    out = build / f"{stem}-{digest.hexdigest()[:16]}.so"
    if out.exists():
        return out
    for cc in ("cc", "gcc", "g++"):
        # Compile to a private temp name and os.replace into place: a
        # killed compile (or a concurrent process — _LOCK is
        # thread-local) must never leave a half-written .so at the
        # cache path, where it would be trusted forever
        tmp = build / f".{stem}-{os.getpid()}.so.tmp"
        try:
            # extra_args trail the sources: -l libraries must follow
            # the objects that use them under --as-needed linkers
            proc = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", *map(str, srcs),
                 "-o", str(tmp), *extra_args],
                capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            tmp.unlink(missing_ok=True)
            continue
        if proc.returncode == 0:
            os.replace(tmp, out)
            # prune superseded builds (incl. the legacy fixed name)
            for old in build.glob(f"{stem}*.so"):
                if old != out:
                    old.unlink(missing_ok=True)
            return out
        tmp.unlink(missing_ok=True)
        logger.debug("%s failed to build %s.so: %s", cc, stem,
                     proc.stderr)
    return None


def _compile() -> Path | None:
    return _compile_src(_SRCS, "jlog", extra_args=("-lz",))


def jlog() -> ctypes.CDLL | None:
    """The compiled codec, or None (callers use the Python path)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _LOCK:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            path = _compile()
            if path is None:
                return None
            lib = ctypes.CDLL(str(path))
            lib.jlog_scan.restype = ctypes.c_int64
            lib.jlog_scan.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64)]
            lib.jlog_frame.restype = ctypes.c_int64
            lib.jlog_frame.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_char_p]
            lib.jt_realtime_edges.restype = ctypes.c_int64
            lib.jt_realtime_edges.argtypes = [
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
            lib.jt_topo_order.restype = ctypes.c_int64
            lib.jt_topo_order.argtypes = [
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64)]
            lib.jt_edge_layout.restype = ctypes.c_int64
            i32 = ctypes.POINTER(ctypes.c_int32)
            lib.jt_edge_layout.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i32, i32,
                ctypes.POINTER(ctypes.c_uint8), i32, i32, i32, i32]
            lib.jt_off_spine.restype = ctypes.c_int64
            lib.jt_off_spine.argtypes = [ctypes.c_int64] + [
                ctypes.POINTER(ctypes.c_int64)] * 6
            _lib = lib
        except Exception:  # noqa: BLE001 — never break the store
            logger.exception("loading native jlog codec failed")
            _lib = None
        return _lib


def scan(buf: bytes, start: int) -> tuple[list[tuple[int, int]], int]:
    """(payload (start, end) offsets, valid_prefix_end) via the C
    codec; raises RuntimeError if the codec is unavailable."""
    import numpy as np

    lib = jlog()
    if lib is None:
        raise RuntimeError("native jlog codec unavailable")
    # generous bound: every record needs >= 8 bytes of header
    max_records = max((len(buf) - start) // 8 + 1, 1)
    offsets = (ctypes.c_int64 * (2 * max_records))()
    valid_end = ctypes.c_int64(start)
    n = lib.jlog_scan(buf, len(buf), start, offsets, max_records,
                      ctypes.byref(valid_end))
    # one C-speed materialization — per-item ctypes access costs more
    # than the scan itself
    arr = np.ctypeslib.as_array(offsets)[:2 * n].reshape(-1, 2)
    return arr.tolist(), int(valid_end.value)


def frame(payloads: list[bytes]) -> bytes:
    """Concatenated framed records for payloads via the C codec;
    raises RuntimeError if unavailable."""
    lib = jlog()
    if lib is None:
        raise RuntimeError("native jlog codec unavailable")
    blob = b"".join(payloads)
    lens = (ctypes.c_int64 * len(payloads))(*map(len, payloads))
    out = ctypes.create_string_buffer(len(blob) + 8 * len(payloads))
    written = lib.jlog_frame(blob, lens, len(payloads), out)
    return out.raw[:written]


# ---------------------------------------------------------------------------
# elleflat: C-API flattener for the elle device engine
# ---------------------------------------------------------------------------

_EF_SRC = Path(__file__).with_name("elleflat.c")
_ef_lib = None
_ef_tried = False

# field ids — must match elleflat.c's enum
EF_APPEND_FIELDS = ("t_type", "t_proc", "t_inv", "t_comp", "t_opidx",
                    "ap_txn", "ap_key", "ap_val",
                    "rd_txn", "rd_key", "rd_len", "re_vals", "flag_rd")
EF_RW_FIELDS = ("t_type", "t_proc", "t_inv", "t_comp", "t_opidx",
                "wr_txn", "wr_key", "wr_val", "wr_nonfinal",
                "rd_txn", "rd_key", "rd_val",
                "fr_txn", "fr_key", "fr_prev", "fr_new",
                "er_txn", "er_key", "er_val", "int_row", "int_expected")


def _compile_ef() -> Path | None:
    import sysconfig

    inc = sysconfig.get_paths().get("include")
    if not inc:
        return None
    return _compile_src((_EF_SRC,), "elleflat",
                        extra_args=(f"-I{inc}",))


def elleflat() -> ctypes.PyDLL | None:
    """The compiled flattener (PyDLL: it calls the CPython C-API under
    the GIL), or None — callers use the Python flattening path."""
    global _ef_lib, _ef_tried
    if _ef_lib is not None or _ef_tried:
        return _ef_lib
    with _LOCK:
        if _ef_lib is not None or _ef_tried:
            return _ef_lib
        _ef_tried = True
        try:
            path = _compile_ef()
            if path is None:
                return None
            lib = ctypes.PyDLL(str(path))
            lib.ef_flatten.restype = ctypes.c_void_p
            lib.ef_flatten.argtypes = [ctypes.py_object, ctypes.c_int64]
            lib.ef_status.restype = ctypes.c_int64
            lib.ef_status.argtypes = [ctypes.c_void_p]
            lib.ef_len.restype = ctypes.c_int64
            lib.ef_len.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.ef_copy.restype = None
            lib.ef_copy.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.POINTER(ctypes.c_int64)]
            lib.ef_keys.restype = ctypes.py_object
            lib.ef_keys.argtypes = [ctypes.c_void_p]
            lib.ef_free.restype = None
            lib.ef_free.argtypes = [ctypes.c_void_p]
            _ef_lib = lib
        except Exception:  # noqa: BLE001 — flattening has a Python path
            logger.exception("loading native elleflat failed")
            _ef_lib = None
        return _ef_lib


class NotVectorizable(Exception):
    """The native flattener found non-int values / too many keys."""


def elle_flatten(ops: list, kind: int) -> tuple[dict, list]:
    """One C pass over a history's op list. kind 0 = list-append,
    1 = rw-register. Returns ({field: int64 array}, key list); raises
    RuntimeError if the native flattener is unavailable and
    NotVectorizable when the history can't take the int fast path."""
    import numpy as np

    lib = elleflat()
    if lib is None:
        raise RuntimeError("native elleflat unavailable")
    h = lib.ef_flatten(ops, kind)
    if not h:
        raise RuntimeError("native elleflat failed")
    try:
        if lib.ef_status(h):
            raise NotVectorizable()
        fields = EF_RW_FIELDS if kind else EF_APPEND_FIELDS
        out = {}
        p = ctypes.POINTER(ctypes.c_int64)
        for fid, name in enumerate(fields):
            n = lib.ef_len(h, fid)
            arr = np.empty(n, dtype=np.int64)
            if n:
                lib.ef_copy(h, fid, arr.ctypes.data_as(p))
            out[name] = arr
        keys = lib.ef_keys(h)
        return out, keys
    finally:
        lib.ef_free(h)


def realtime_edges(inv, comp):
    """(src_idx, dst_idx) int64 arrays of reduced realtime-order edges
    over dense txn positions, via the C sweep (order.c); raises
    RuntimeError if the codec is unavailable. inv/comp are int64
    arrays of invocation/completion history positions."""
    import numpy as np

    lib = jlog()
    if lib is None or not hasattr(lib, "jt_realtime_edges"):
        raise RuntimeError("native order sweep unavailable")
    inv = np.ascontiguousarray(inv, dtype=np.int64)
    comp = np.ascontiguousarray(comp, dtype=np.int64)
    n = len(inv)
    if n == 0:
        return (np.empty(0, dtype=np.int64),
                np.empty(0, dtype=np.int64))
    p = ctypes.POINTER(ctypes.c_int64)
    cap = max(8 * n, 1024)
    while True:
        src = np.empty(cap, dtype=np.int64)
        dst = np.empty(cap, dtype=np.int64)
        m = lib.jt_realtime_edges(
            inv.ctypes.data_as(p), comp.ctypes.data_as(p), n,
            src.ctypes.data_as(p), dst.ctypes.data_as(p), cap)
        if m == -1:
            cap *= 4
            continue
        if m < 0:
            raise RuntimeError(f"native order sweep failed ({m})")
        return src[:m].copy(), dst[:m].copy()


def edge_layout(recv, send, on, n_pad: int, blocks: int):
    """One pass's SCC edge layout (order.c's jt_edge_layout): (recv,
    send, off, end) as int32 arrays, the edges of each block counting-
    sorted by receiver. Raises RuntimeError if the codec is unavailable
    or a live receiver is out of range."""
    import numpy as np

    lib = jlog()
    if lib is None or not hasattr(lib, "jt_edge_layout"):
        raise RuntimeError("native edge layout unavailable")
    recv = np.ascontiguousarray(recv, dtype=np.int32)
    send = np.ascontiguousarray(send, dtype=np.int32)
    on = np.ascontiguousarray(on, dtype=np.uint8)
    outs = [np.empty(len(recv), dtype=np.int32) for _ in range(3)]
    outs.append(np.empty(blocks * n_pad, dtype=np.int32))
    p = ctypes.POINTER(ctypes.c_int32)
    rc = lib.jt_edge_layout(
        n_pad, blocks, len(recv), recv.ctypes.data_as(p),
        send.ctypes.data_as(p), on.ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint8)),
        *(o.ctypes.data_as(p) for o in outs))
    if rc < 0:
        raise RuntimeError(f"native edge layout failed ({rc})")
    return tuple(outs)


def off_spine(rd_key, rd_len, sp_off, sp_vals, re_vals):
    """The read elements that differ from their key's spine at their
    position (order.c's jt_off_spine), as ascending int64 indices into
    re_vals. Raises RuntimeError if the codec is unavailable."""
    import numpy as np

    lib = jlog()
    if lib is None or not hasattr(lib, "jt_off_spine"):
        raise RuntimeError("native prefix pass unavailable")
    args = [np.ascontiguousarray(x, dtype=np.int64)
            for x in (rd_key, rd_len, sp_off, sp_vals, re_vals)]
    out = np.empty(len(args[-1]), dtype=np.int64)
    p = ctypes.POINTER(ctypes.c_int64)
    m = lib.jt_off_spine(len(args[0]), *(x.ctypes.data_as(p) for x in args),
                         out.ctypes.data_as(p))
    return out[:m].copy()


def topo_order(n: int, src, dst):
    """The Kahn order (order.c's jt_topo_order) of the graph on nodes
    0..n-1 with edges src[i] -> dst[i], as an int64 array: every node
    when the graph is acyclic, fewer when some node lies on or behind a
    cycle. Raises RuntimeError if the codec is unavailable."""
    import numpy as np

    lib = jlog()
    if lib is None or not hasattr(lib, "jt_topo_order"):
        raise RuntimeError("native topological order unavailable")
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    out = np.empty(max(n, 1), dtype=np.int64)
    p = ctypes.POINTER(ctypes.c_int64)
    m = lib.jt_topo_order(n, src.ctypes.data_as(p), dst.ctypes.data_as(p),
                          len(src), out.ctypes.data_as(p))
    if m < 0:
        raise RuntimeError(f"native topological order failed ({m})")
    return out[:m].copy()
