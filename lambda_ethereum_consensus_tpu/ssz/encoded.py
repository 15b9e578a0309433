"""Encoded image of a container's big fields: delta-driven serialization.

A node persists every block's post-state as its complete SSZ
(store/state_store.py), and the slot-to-slot *delta* of a 2^20-validator
state is tiny — participation flags, a few hundred balances, one row each
of the history vectors — while ``Container.serialize`` walks every
``Validator`` in Python: 9.3 s of a 13.3 s block import went to producing
127 MB of registry bytes that no plain block changes.  The state's *root*
has been incremental since round 13 (ssz/incremental.py); this is the same
idea for its *encoding*.

``EncodedImage`` keeps, per big field (a ``List``/``Vector`` of fixed-size
elements), the ``(n, element size)`` byte array of its serialization and
the ``TrackedList`` instance + generation the array matches.  On the next
encode it asks ``state_transition.mutable.dirty_superset`` — the one
delta-chain walk, shared with the root engine and the resident plane —
which indices may differ:

- an empty set: the array is **reused** as it stands;
- a set: those elements are re-serialized into it (**patched**; appended
  rows grow it);
- ``None`` (plain or foreign list, branched lineage, a structural
  mutation, a cut chain) or most of the field: the array is **rebuilt**
  whole through the column-wise ``_serialize_rows``.

Never a guess: the fallback is always the exact full build, a malformed
value always reaches the element loop so the same ``SSZError`` is raised,
and every path's bytes are pinned to the ``Container.serialize`` loop in
tests/unit/test_state_encode.py.  ``encode`` returns an immutable
``bytes`` copied out of the arrays, never a view of them: the next
encode patches the arrays in place.

One image tracks ONE state lineage (it rides freeze/thaw as
``state._encoded_image``, beside ``_root_engine``); fed two branches
alternately it stays exact and rebuilds on every switch.
"""

from __future__ import annotations

import numpy as np

from ..config import get_chain_spec
from .core import _BATCH_MIN, _METRICS, List, Vector, _assemble, _serialize_rows

__all__ = ["EncodedImage"]

# a field whose possibly-dirty fraction exceeds this is rebuilt whole:
# gathering and scattering most of a list costs more than one column pass
_REBUILD_FRACTION = 4


class _FieldImage:
    __slots__ = ("rows", "last_list", "stamp_gen")

    def __init__(self, rows: np.ndarray):
        self.rows = rows  # (n, element size) uint8, writable, C-contiguous
        # the exact TrackedList instance ``rows`` matches, and its
        # mutation generation at that instant (None: nothing to walk to)
        self.last_list = None
        self.stamp_gen = -1


class EncodedImage:
    """Stateful ``serialize`` for one evolving container lineage."""

    def __init__(self, cls: type):
        self.cls = cls
        self._fields: dict[str, _FieldImage] = {}
        self._spec_name = None

    def encode(self, value, spec=None) -> bytes:
        spec = spec or get_chain_spec()
        if self._spec_name != spec.name:
            # config swap invalidates every cached length/shape
            self._fields.clear()
            self._spec_name = spec.name
        return _assemble(
            self.cls, value, spec, lambda f, t, v: self._field_bytes(f, t, v, spec)
        )

    def retained_bytes(self) -> int:
        """Host bytes held by the field arrays (the lists they stamp are
        the lineage's own)."""
        return sum(int(img.rows.nbytes) for img in self._fields.values())

    def _field_bytes(self, fname, ftype, value, spec):
        if not (
            isinstance(ftype, (List, Vector))
            and ftype.elem.is_fixed_size(spec)
            and len(value) >= _BATCH_MIN
        ):
            return ftype.serialize(value, spec)
        if isinstance(ftype, List):
            ftype._check_limit(value, spec)
        else:
            ftype._check_len(value, spec)
        path = self._refresh(fname, ftype.elem, value, spec)
        if path is None:
            # an element type the column builder does not specialize, or
            # a malformed value: the loop gives the bytes or the SSZError
            self._fields.pop(fname, None)
            return ftype.serialize(value, spec)
        _METRICS.inc("state_encode_fields_total", field=fname, path=path)
        return memoryview(self._fields[fname].rows).cast("B")

    def _refresh(self, fname, elem, value, spec) -> str | None:
        """Bring the field's array level with ``value`` and re-stamp it;
        returns the path taken, ``None`` when no array can be had."""
        from ..state_transition.mutable import dirty_superset

        n = len(value)
        img = self._fields.get(fname)
        delta = None
        if img is not None:
            delta = dirty_superset(value, img.last_list, img.stamp_gen)
            kept = len(img.rows)
            if delta is not None and not (
                # the log must account for the length: every appended row
                # is in it, and a shrink is structural (guard anyway)
                n >= kept
                and max(delta, default=-1) < n
                and all(i in delta for i in range(kept, n))
            ):
                delta = None
        if delta is None or len(delta) > n // _REBUILD_FRACTION:
            rows = _serialize_rows(elem, value, spec)
            if rows is None:
                return None
            if not rows.flags.writeable:
                rows = rows.copy()
            img = self._fields[fname] = _FieldImage(rows)
            path = "rebuilt"
        elif delta:
            dirty = sorted(delta)
            sub = _serialize_rows(elem, [value[i] for i in dirty], spec)
            if sub is None:
                return None
            if n > len(img.rows):
                grown = np.empty((n, img.rows.shape[1]), np.uint8)
                grown[: len(img.rows)] = img.rows
                img.rows = grown
            img.rows[dirty] = sub
            path = "patched"
        else:
            path = "reused"
        gen = getattr(value, "gen", None)
        img.last_list, img.stamp_gen = (None, -1) if gen is None else (value, gen)
        return path
