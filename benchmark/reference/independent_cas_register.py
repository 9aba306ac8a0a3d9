"""Plain reference for the independent-cas-register deployment:
linearizability of every key of an independent-key register history,
each key on its own, with Knossos's cas-register semantics.

It imports nothing of the program. It splits the history by key itself
(an op's value is the pair (key, value), as jepsen.independent/tuple
makes it), unwraps the values, and decides each key with the decider of
`cas_register.py` beside it, loaded from this directory: by proof where
the generator's own application order, restricted to the key, replays
under the register's semantics and real-time order; by its crash-aware
search where it does not.

A key's certificate maps to the key's ops so: its `key` names the key,
its `history` digest counts the ops of the whole history, and every op
index in its orders and witness is an index (position) in the whole
history, which this module maps to the op's position in the key's own
history before `cas_register.replay` replays it there.
"""

from __future__ import annotations

import importlib.util
import os


def _load_register():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "cas_register.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_cas_register_for_keys", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reg = _load_register()


class Key:
    """One key's history: its events with unwrapped values, in order,
    and the whole history's position of each."""

    __slots__ = ("events", "positions", "local")

    def __init__(self):
        self.events: list = []
        self.positions: list = []
        self.local: dict | None = None

    def local_of(self, pos):
        if self.local is None:
            self.local = {g: i for i, g in enumerate(self.positions)}
        return self.local.get(pos)


def split(events) -> dict:
    """key -> Key, for events (type, process, f, (key, value))."""
    out: dict = {}
    for pos, (t, p, f, kv) in enumerate(events):
        k, v = kv
        key = out.get(k)
        if key is None:
            key = out[k] = Key()
        key.events.append((t, p, f, v))
        key.positions.append(pos)
    return out


def _local_witnesses(split_: dict, witness) -> dict:
    """key -> the generator's application order restricted to the key,
    in the key's own positions (one pass over the witness)."""
    owner: dict = {}
    for k, key in split_.items():
        for i, g in enumerate(key.positions):
            owner[g] = (k, i)
    out: dict = {k: [] for k in split_}
    for g in witness:
        k, i = owner[g]
        out[k].append(i)
    return out


def verdicts(split_: dict, witness=None, check_reads: bool = True,
             search: bool = False) -> dict:
    """key -> the reference verdict of the key's history. `witness`:
    the generator's application order over the whole history, by
    position. check_reads=False is the control: reads are not held to
    the register's value."""
    ws = _local_witnesses(split_, witness) if witness is not None else {}
    return {k: reg.verdict(key.events, ws.get(k),
                           search=search or witness is None,
                           check_reads=check_reads)
            for k, key in split_.items()}


def _map_steps(steps, key: Key):
    out = []
    for item in steps:
        if not isinstance(item, (list, tuple)) or len(item) != 2:
            raise ValueError(f"malformed step {item!r}")
        i = key.local_of(item[0])
        if i is None:
            raise ValueError(f"op {item[0]!r} is not an op of this key")
        out.append((i, item[1]))
    return out


def replay_key(key: Key, k, n_events: int, result: dict) -> str | None:
    """None when the key's result carries a certificate that proves its
    verdict on the key's history; otherwise the reason it does not."""
    cert = (result or {}).get("certificate")
    if not isinstance(cert, dict) or "absent" in cert:
        return f"no certificate ({(cert or {}).get('absent')})"
    if cert.get("key") != k:
        return f"certificate names key {cert.get('key')!r}, not {k!r}"
    if (cert.get("history") or {}).get("ops") != n_events:
        return (f"certificate's history digest counts "
                f"{(cert.get('history') or {}).get('ops')!r} ops, the "
                f"history {n_events}")
    try:
        mapped = dict(cert)
        mapped["segments"] = [
            {"order": _map_steps(seg["order"], key)}
            for seg in cert.get("segments") or []]
        w = cert.get("witness")
        if isinstance(w, dict):
            mapped["witness"] = dict(
                w, prefix=_map_steps(w.get("prefix") or [], key),
                **{"op-index": key.local_of(w.get("op-index"))})
    except (ValueError, TypeError, KeyError) as e:
        return str(e)
    return reg.replay(key.events, dict(result, certificate=mapped))
