"""The comparison that decides `correct`.

The reference is the plain host model: what every key holds follows from
the operations alone (benchmarks/data.py makes the fill from the seed, the
generators log what they sent and when it was acknowledged).  The traffic
is built so that this is well defined under concurrency: every element is
added once, by one client, and removed only by that client after the add
was acknowledged; counter updates are positive increments.  So

* once everything has stopped, a key holds exactly: its fill, plus every
  acknowledged add, minus every acknowledged remove (or the sum of the
  acknowledged increments) -- `readback_wrong` counts answers that differ;
* an answer given while others write is held between two bounds that the
  configuration's guarantees fix.  With [s, r] the instants the read's
  snapshot was asked for and granted (the read itself, or the start of its
  transaction):
    must hold  every element whose add was acknowledged before s and whose
               remove, if any, was sent after r
    may hold   only elements whose add was sent before r and whose remove,
               if any, was acknowledged after s
  (a counter: between the sum acknowledged before s and the sum sent
  before r).  `window_wrong` counts answers outside their bounds.

Both limits are 0: the comparison is exact.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from benchmarks import data

INF = float("inf")


class History:
    """The operations of a run, indexed for the bounds above."""

    def __init__(self, fill: dict, seed: int, update_logs):
        self.fill, self.seed = fill, seed
        self.is_set = fill["type"] == "set_aw"
        # sets: key -> {element: [add_sent, add_acked, rm_sent, rm_acked]}
        self.elems = defaultdict(dict)
        # counters: key -> ([ack instants], [amounts]), ([sent], [amounts])
        self.incs = defaultdict(lambda: ([], []))
        self.unsure = set()      # keys with an update that got no answer
        for i, op, arg, sent, acked, _rec, _cid in update_logs:
            if acked is None:
                self.unsure.add(i)
            if not self.is_set:
                self.incs[i][0].append((acked if acked is not None else INF,
                                        arg))
                self.incs[i][1].append((sent, arg))
            elif op == "add":
                self.elems[i][arg] = [sent, acked if acked is not None
                                      else INF, None, None]
            elif op == "remove":
                e = self.elems[i].get(arg)
                if e is None:      # an element of the fill
                    e = self.elems[i][arg] = [-INF, -INF, None, None]
                e[2] = sent
                e[3] = acked if acked is not None else INF
            else:
                raise ValueError(f"unknown logged op {op!r}")
        self._sums = {}
        for i, (acks, sents) in self.incs.items():
            self._sums[i] = tuple(self._prefix(x) for x in (acks, sents))

    @staticmethod
    def _prefix(pairs):
        pairs.sort()
        ts, cum, acc = [], [], 0
        for t, a in pairs:
            acc += a
            ts.append(t)
            cum.append(acc)
        return ts, cum

    @staticmethod
    def _before(prefix, t) -> int:
        ts, cum = prefix
        n = bisect.bisect_left(ts, t)
        return cum[n - 1] if n else 0

    # -- the end state ------------------------------------------------------
    def final(self, i: int):
        base = data.fill_value(self.fill, self.seed, i)
        if not self.is_set:
            return base + sum(a for t, a in self.incs[i][0] if t < INF) \
                if i in self.incs else base
        for e, (_s, acked, _rs, racked) in self.elems.get(i, {}).items():
            if racked is not None and racked < INF:
                base.discard(e)
            elif acked < INF:
                base.add(e)
        return base

    def bounds(self, i, s, r):
        """For the report of a wrong counter answer: (least, most, ms
        between the newest acknowledgement it had to show and its send)."""
        if self.is_set or i not in self._sums:
            return ()
        base = data.fill_value(self.fill, self.seed, i)
        acks, sents = self._sums[i]
        n = bisect.bisect_left(acks[0], s)
        newest = round((s - acks[0][n - 1]) * 1e3, 3) if n else None
        return (base + self._before(acks, s), base + self._before(sents, r),
                newest)

    # -- one answer given inside the window -----------------------------------
    def answer_ok(self, i: int, s: float, r: float, val) -> bool:
        if not self.is_set:
            base = data.fill_value(self.fill, self.seed, i)
            if i not in self._sums:
                return val == base
            acks, sents = self._sums[i]
            return (base + self._before(acks, s) <= val
                    <= base + self._before(sents, r))
        got = set(val)
        logged = self.elems.get(i, {})
        filled = data.fill_value(self.fill, self.seed, i)
        for e in got:
            t = logged.get(e)
            if t is None:
                if e not in filled:
                    return False             # nobody added it, or the fill
                continue                     # took it away again
            if t[0] > r or (t[3] is not None and t[3] < s):
                return False                 # from the future, or removed
        for e in filled:
            if e not in logged and e not in got:
                return False                 # a filled element is missing
        for e, t in logged.items():
            if e not in got and t[1] < s and (t[2] is None or t[2] > r):
                return False                 # an acknowledged add is missing
        return True


def _short(val):
    return list(val)[:6] if isinstance(val, (list, tuple)) else val


def compare(fill, seed, logs, readback):
    """-> (checks, counts).  `logs` are the generators' (and the warm-up's)
    pickles; `readback` is [(key index, answer)] read once all had stopped."""
    hist = History(fill, seed, [u for g in logs for u in g["updates"]])
    window_wrong = checked = 0
    first_wrong = []
    for g in logs:
        for i, _txn, _t0, t1, s, r, val in g["reads"]:
            if t1 is None:
                continue                     # no answer: counted as failed
            checked += 1
            if not hist.answer_ok(i, s, r, val):
                window_wrong += 1
                if len(first_wrong) < 3:
                    first_wrong.append((i, _txn, _short(val))
                                       + hist.bounds(i, s, r))
    readback_wrong = compared = 0
    for i, val in readback:
        if i in hist.unsure:
            continue
        compared += 1
        want = hist.final(i)
        got = set(val) if hist.is_set else val
        if got != want:
            readback_wrong += 1
            if len(first_wrong) < 6:
                first_wrong.append((i, "readback", _short(val),
                                    _short(sorted(want)) if hist.is_set
                                    else want))
    never = sum(g["never"] for g in logs)
    checks = {
        "window_wrong": {"value": window_wrong, "limit": 0},
        "readback_wrong": {"value": readback_wrong, "limit": 0},
        "never_answered": {"value": never, "limit": 0},
        "nothing_compared": {"value": int(checked == 0 or compared == 0),
                             "limit": 0},
    }
    counts = {"window_answers_compared": checked,
              "readback_answers_compared": compared,
              "readback_keys_unsure": len(readback) - compared,
              "first_wrong": first_wrong}
    return checks, counts


def verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
