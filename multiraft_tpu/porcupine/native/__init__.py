"""ctypes loader for the native KV linearizability checker.

Builds ``libporcupine.<source hash>.so`` from ``checker.cpp`` on first
use (g++ -O2; no pybind11 in this image — plain C ABI + ctypes) and exposes
:func:`check_kv_partition_native` (verdict only) and
:func:`check_kv_partition_native_verbose` (verdict + partial
linearizations, the reference's computePartial).  Falls back to the
Python DFS when the toolchain is unavailable.  Partition size is
unbounded — the C++ DFS memoizes through a 128-bit hash, not a
fixed-width bitset.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Tuple

from ...utils.native_build import build_and_load

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "checker.cpp")
_SO = os.path.join(_HERE, "libporcupine.so")

_lib = None
_build_failed = False

_COMMON_ARGS = [
    ctypes.c_int32,
    ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_uint8),
    ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_char_p),
    ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_char_p),
    ctypes.POINTER(ctypes.c_int32),
    ctypes.c_int64,   # max_steps (0 = unlimited)
    ctypes.c_double,  # max_wall_s (0 = unlimited); checked in-loop
]


# Transition callback for the model-generic DFS:
# (state_id, op_id, *new_state_id) -> 1 legal / 0 illegal / <0 error.
STEP_CB = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.c_int32, ctypes.c_int32,
    ctypes.POINTER(ctypes.c_int32),
)

_GENERIC_ARGS = [
    ctypes.c_int32,
    ctypes.POINTER(ctypes.c_int32),
    ctypes.POINTER(ctypes.c_uint8),
    STEP_CB,
    ctypes.c_int64,   # max_steps (0 = unlimited)
    ctypes.c_double,  # max_wall_s (0 = unlimited)
]


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    try:
        lib = build_and_load(_SRC, _SO)
        lib.check_kv_partition.restype = ctypes.c_int
        lib.check_kv_partition.argtypes = list(_COMMON_ARGS)
        lib.check_kv_partition_verbose.restype = ctypes.c_int
        lib.check_kv_partition_verbose.argtypes = list(_COMMON_ARGS) + [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.check_generic_partition.restype = ctypes.c_int
        lib.check_generic_partition.argtypes = list(_GENERIC_ARGS) + [
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.check_generic_partition_verbose.restype = ctypes.c_int
        lib.check_generic_partition_verbose.argtypes = list(_GENERIC_ARGS) + [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.mrt_buf_free.restype = None
        lib.mrt_buf_free.argtypes = [ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
        return lib
    except Exception:
        _build_failed = True
        return None


def native_available() -> bool:
    return _load() is not None


def _marshal(events, op_kinds, op_values, op_outputs):
    n = len(op_kinds)
    # A malformed event order (return before call, duplicate events)
    # would walk the C++ DFS off its linked list — reject it here with
    # a Python error instead of a segfault.
    seen = bytearray(n)  # 0 = unseen, 1 = called, 2 = returned
    for op, is_ret in events:
        if not (0 <= op < n):
            raise ValueError(f"event references op {op} outside [0,{n})")
        want = 1 if is_ret else 0
        if seen[op] != want:
            raise ValueError(
                f"malformed event order: op {op} "
                + ("returned before call" if is_ret else "called twice")
            )
        seen[op] = want + 1
    if any(s != 2 for s in seen):
        raise ValueError("malformed history: op missing call/return")
    ev_op = (ctypes.c_int32 * len(events))(*[e[0] for e in events])
    ev_ret = (ctypes.c_uint8 * len(events))(*[1 if e[1] else 0 for e in events])
    kinds = (ctypes.c_int32 * n)(*op_kinds)
    vals = [v.encode() for v in op_values]
    outs = [o.encode() for o in op_outputs]
    val_ptrs = (ctypes.c_char_p * n)(*vals)
    out_ptrs = (ctypes.c_char_p * n)(*outs)
    val_lens = (ctypes.c_int32 * n)(*[len(v) for v in vals])
    out_lens = (ctypes.c_int32 * n)(*[len(o) for o in outs])
    # Keep the bytes objects alive until the call returns.
    keepalive = (vals, outs)
    return (
        n, ev_op, ev_ret, kinds,
        ctypes.cast(val_ptrs, ctypes.POINTER(ctypes.c_char_p)), val_lens,
        ctypes.cast(out_ptrs, ctypes.POINTER(ctypes.c_char_p)), out_lens,
    ), keepalive


def check_kv_partition_native(
    events, op_kinds, op_values, op_outputs, max_steps=0, max_wall_s=0.0
):
    """Run the C++ DFS on one pre-sorted partition.

    events: list of (op_id, is_return) in time order.
    Returns 1 linearizable / 0 illegal / 2 budget exhausted / None if
    native path unavailable (caller falls back to Python).
    """
    lib = _load()
    if lib is None:
        return None
    args, _keep = _marshal(events, op_kinds, op_values, op_outputs)
    return lib.check_kv_partition(*args, max_steps, max_wall_s)


def _parse_partials(lib, buf, buf_len) -> List[List[int]]:
    partials: List[List[int]] = []
    if buf and buf_len.value > 0:
        try:
            flat = buf[: buf_len.value]
            n_seqs = flat[0]
            w = 1
            for _ in range(n_seqs):
                ln = flat[w]
                w += 1
                partials.append(list(flat[w: w + ln]))
                w += ln
        finally:
            lib.mrt_buf_free(buf)
    return partials


def check_generic_partition_native(
    events, n, step_cb, max_steps=0, max_wall_s=0.0,
) -> Optional[Tuple[int, int]]:
    """Run the model-generic C++ DFS on one pre-sorted partition.

    ``step_cb(state_id, op_id, new_state_id_ptr)`` resolves transitions
    (fired once per distinct pair — the C++ side memoizes).  Returns
    ``(rc, steps_done)`` with rc 1 OK / 0 ILLEGAL / 2 budget /
    3 callback error, or None when the native path is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    ev_op = (ctypes.c_int32 * len(events))(*[e[0] for e in events])
    ev_ret = (ctypes.c_uint8 * len(events))(*[1 if e[1] else 0 for e in events])
    cb = STEP_CB(step_cb)
    steps = ctypes.c_int64(0)
    rc = lib.check_generic_partition(
        n, ev_op, ev_ret, cb, max_steps, max_wall_s, ctypes.byref(steps)
    )
    return rc, steps.value


def check_generic_partition_native_verbose(
    events, n, step_cb, max_steps=0, max_wall_s=0.0,
) -> Optional[Tuple[int, List[List[int]], int]]:
    """Verbose generic DFS: ``(rc, partials, steps_done)`` — same
    computePartial evidence as the KV fast path.  None = unavailable."""
    lib = _load()
    if lib is None:
        return None
    ev_op = (ctypes.c_int32 * len(events))(*[e[0] for e in events])
    ev_ret = (ctypes.c_uint8 * len(events))(*[1 if e[1] else 0 for e in events])
    cb = STEP_CB(step_cb)
    steps = ctypes.c_int64(0)
    buf = ctypes.POINTER(ctypes.c_int32)()
    buf_len = ctypes.c_int64(0)
    rc = lib.check_generic_partition_verbose(
        n, ev_op, ev_ret, cb, max_steps, max_wall_s,
        ctypes.byref(buf), ctypes.byref(buf_len), ctypes.byref(steps),
    )
    return rc, _parse_partials(lib, buf, buf_len), steps.value


def check_kv_partition_native_verbose(
    events, op_kinds, op_values, op_outputs, max_steps=0, max_wall_s=0.0
) -> Optional[Tuple[int, List[List[int]]]]:
    """Verbose C++ DFS: returns ``(rc, partials)`` where partials is
    the reference computePartial output — op-id sequences, the single
    full linearization on OK, the distinct longest linearizable
    prefixes otherwise.  None = native path unavailable."""
    lib = _load()
    if lib is None:
        return None
    args, _keep = _marshal(events, op_kinds, op_values, op_outputs)
    buf = ctypes.POINTER(ctypes.c_int32)()
    buf_len = ctypes.c_int64(0)
    rc = lib.check_kv_partition_verbose(
        *args, max_steps, max_wall_s, ctypes.byref(buf), ctypes.byref(buf_len)
    )
    return rc, _parse_partials(lib, buf, buf_len)
