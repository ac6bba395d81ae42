"""The plain reference: add-wins sets and integer counters in Python dicts.

Copied from chip_smoke.py's `Model` (PR 21) and extended with a commit
history, so that a read at an earlier snapshot has an answer.  It shares no
code with antidote_tpu/ and is handed nothing the program made: it is fed
the same operations, made from the seed.
"""

from __future__ import annotations

import bisect


class Model:
    """State after every commit; `value(obj, at)` reads at commit `at`."""

    def __init__(self):
        self.commit_no = 0
        # key -> ([commit numbers], [value after that commit])
        self._hist: dict = {}

    def _head(self, key, ty):
        h = self._hist.get(key)
        if h is None:
            return frozenset() if ty == "set_aw" else 0
        return h[1][-1]

    def apply(self, updates) -> int:
        """One transaction: every update lands at one new commit number."""
        self.commit_no += 1
        staged: dict = {}
        for key, ty, _bucket, (op, arg) in updates:
            cur = staged[key] if key in staged else self._head(key, ty)
            if ty == "counter_pn":
                cur = cur + (arg if op == "increment" else -arg)
            elif op == "add_all":
                cur = cur | frozenset(arg)
            elif op == "add":
                cur = cur | {arg}
            elif op == "remove":
                # observed-remove: takes away what this snapshot holds; in
                # one DC with serial commits that is the element itself
                cur = cur - {arg}
            else:
                raise ValueError(f"unknown op {op!r} for {ty}")
            staged[key] = cur
        for key, cur in staged.items():
            nos, vals = self._hist.setdefault(key, ([], []))
            nos.append(self.commit_no)
            vals.append(cur)
        return self.commit_no

    def value(self, obj, at: int | None = None):
        key, ty, _bucket = obj
        h = self._hist.get(key)
        cur = frozenset() if ty == "set_aw" else 0
        if h is not None:
            i = len(h[0]) if at is None else bisect.bisect_right(h[0], at)
            if i:
                cur = h[1][i - 1]
        return sorted(cur) if ty == "set_aw" else cur
