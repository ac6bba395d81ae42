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

Under geo-replication (a configuration with `dcs`: N data centres, each
answer tagged with the DC that gave it, its home) Cure promises causal+
consistency: a DC shows its own acknowledged commits at once, and other
DCs' commits later, in causal order.  A client sends everything to its
home DC, so an element's add and remove come from one DC.  The bounds
above become:
    must hold  every add acknowledged at the answer's home DC before s
               whose remove, if any, was sent after r (and the fill, which
               every DC holds before the window)
    may hold   only elements whose add was sent (at any DC) before r, and
               none whose remove was acknowledged at the home DC before s
  (a counter: between the home DC's increments acknowledged before s and
  all increments sent before r), and one more rule, `causal_wrong`: an
  answer that holds an element e that client c added to the key must hold
  every element c added there with an add acknowledged before e's add was
  sent (unless c sent its remove before r), and must lack every element
  whose remove by c was acknowledged before e's add was sent.  Once every
  DC has stopped, every DC must hold the same end state:  `readback_wrong`
  sums the keys that differ, over the DCs.  With one DC every rule is the
  one above and `causal_wrong` is not reported.

Every limit is 0: the comparison is exact.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from benchmarks import data

INF = float("inf")


class History:
    """The operations of a run, indexed for the bounds above.

    An update is logged as (key, op, arg, sent, acked, recorded, client) and
    under geo-replication one more field, the DC it was sent to (0 where
    it is left out)."""

    def __init__(self, fill: dict, seed: int, update_logs):
        self.fill, self.seed = fill, seed
        self.is_set = fill["type"] == "set_aw"
        # sets: key -> {element: [add_sent, add_acked, rm_sent, rm_acked,
        #                         dc, client]}
        self.elems = defaultdict(dict)
        # counters: key -> ([(acked, amount, dc)], [(sent, amount)])
        self.incs = defaultdict(lambda: ([], []))
        self.unsure = set()      # keys with an update that got no answer
        for u in update_logs:
            i, op, arg, sent, acked, _rec, cid = u[:7]
            dc = u[7] if len(u) > 7 else 0
            if acked is None:
                self.unsure.add(i)
            if not self.is_set:
                self.incs[i][0].append((acked if acked is not None else INF,
                                        arg, dc))
                self.incs[i][1].append((sent, arg))
            elif op == "add":
                self.elems[i][arg] = [sent, acked if acked is not None
                                      else INF, None, None, dc, cid]
            elif op == "remove":
                e = self.elems[i].get(arg)
                if e is None:      # an element of the fill
                    e = self.elems[i][arg] = [-INF, -INF, None, None, dc,
                                              None]
                e[2] = sent
                e[3] = acked if acked is not None else INF
            else:
                raise ValueError(f"unknown logged op {op!r}")
        # counters: the increments acknowledged, by the DC that took them
        self._sums = {}
        for i, (acks, sents) in self.incs.items():
            by_dc = defaultdict(list)
            for t, a, dc in acks:
                by_dc[dc].append((t, a))
            self._sums[i] = ({dc: self._prefix(x) for dc, x in by_dc.items()},
                             self._prefix(sents))
        # sets: a client's elements of a key, for the causal rule
        self._by_client = defaultdict(list)
        for i, es in self.elems.items():
            for e, t in es.items():
                if t[5] is not None:
                    self._by_client[i, t[5]].append((e, t))

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
        if prefix is None:
            return 0
        ts, cum = prefix
        n = bisect.bisect_left(ts, t)
        return cum[n - 1] if n else 0

    # -- the end state ------------------------------------------------------
    def final(self, i: int):
        base = data.fill_value(self.fill, self.seed, i)
        if not self.is_set:
            return base + sum(a for t, a, _dc in self.incs[i][0] if t < INF) \
                if i in self.incs else base
        for e, t in self.elems.get(i, {}).items():
            acked, racked = t[1], t[3]
            if racked is not None and racked < INF:
                base.discard(e)
            elif acked < INF:
                base.add(e)
        return base

    def bounds(self, i, s, r, dc=0):
        """For the report of a wrong counter answer: (least, most, ms
        between the newest acknowledgement it had to show and its send)."""
        if self.is_set or i not in self._sums:
            return ()
        base = data.fill_value(self.fill, self.seed, i)
        acks, sents = self._sums[i]
        home = acks.get(dc)
        n = bisect.bisect_left(home[0], s) if home else 0
        newest = round((s - home[0][n - 1]) * 1e3, 3) if n else None
        return (base + self._before(home, s), base + self._before(sents, r),
                newest)

    # -- one answer given inside the window -----------------------------------
    def answer_ok(self, i: int, s: float, r: float, val, dc: int = 0) -> bool:
        """Within the bounds of an answer of DC `dc` (its home)."""
        if not self.is_set:
            base = data.fill_value(self.fill, self.seed, i)
            if i not in self._sums:
                return val == base
            acks, sents = self._sums[i]
            return (base + self._before(acks.get(dc), s) <= val
                    <= base + self._before(sents, r))
        return self._set_breach(i, s, r, val, dc) is None

    def _set_breach(self, i, s, r, val, dc):
        """The first bound a set answer of DC `dc` breaks, as (rule,
        element), or None."""
        got = set(val)
        logged = self.elems.get(i, {})
        filled = data.fill_value(self.fill, self.seed, i)
        for e in got:
            t = logged.get(e)
            if t is None:
                if e not in filled:
                    return "nobody added", e
                continue                     # the fill took it away again
            if t[0] > r or (t[3] is not None and t[3] < s and t[4] == dc):
                return "from the future or removed", e
        for e in filled:
            if e not in logged and e not in got:
                return "fill missing", e
        for e, t in logged.items():
            if (e not in got and t[1] < s and t[4] == dc
                    and (t[2] is None or t[2] > r)):
                return "home ack missing", e
        return None

    def explain(self, i: int, s: float, r: float, val, dc: int = 0):
        """For the report of a wrong set answer: the first rule it breaks,
        its element and that element's instants in ms from s (add sent,
        add acknowledged, remove sent, remove acknowledged), the DC and
        the client that added it."""
        breach = self._set_breach(i, s, r, val, dc) if self.is_set else None
        if breach is None:
            return ()
        t = self.elems.get(i, {}).get(breach[1])
        ms = lambda x: None if x is None else round((x - s) * 1e3, 3)  # noqa: E731
        return breach + (() if t is None else (
            tuple(ms(x) for x in t[:4]) + (t[4], t[5])))

    def causal_ok(self, i: int, r: float, val) -> bool:
        """A set answer holds each client's adds of the key in the order
        the client saw them acknowledged (see the module's docstring)."""
        if not self.is_set:
            return True
        logged = self.elems.get(i, {})
        newest = {}              # client -> latest add sent among the answer's
        for e in val:
            t = logged.get(e)
            if t is not None and t[5] is not None and t[0] > newest.get(
                    t[5], -INF):
                newest[t[5]] = t[0]
        got = set(val)
        for cid, sent in newest.items():
            for e, t in self._by_client[i, cid]:
                if t[1] < sent and e not in got and (t[2] is None
                                                     or t[2] > r):
                    return False         # an earlier add of c is missing
                if t[3] is not None and t[3] < sent and e in got:
                    return False         # an earlier remove of c is missing
        return True


def _short(val):
    return list(val)[:6] if isinstance(val, (list, tuple)) else val


def readback_diff(hist, readback):
    """-> (compared, [(key index, answer, wanted)] that differ): one DC's
    answers read once everything had stopped, against the end state."""
    compared, wrong = 0, []
    for i, val in readback:
        if i in hist.unsure:
            continue
        compared += 1
        want = hist.final(i)
        got = set(val) if hist.is_set else val
        if got != want:
            wrong.append((i, val, want))
    return compared, wrong


def compare(fill, seed, logs, readback, n_dcs=1, hist=None):
    """-> (checks, counts).  `logs` are the generators' (and the warm-up's)
    pickles; `readback` is [(key index, answer)] read once all had stopped,
    and with `n_dcs` > 1 one such list a DC.  A read is logged as (key,
    in a transaction, sent, answered, s, r, answer) and under
    geo-replication one more field, its home DC."""
    if hist is None:
        hist = History(fill, seed, [u for g in logs for u in g["updates"]])
    window_wrong = causal_wrong = checked = 0
    first_wrong = []
    for g in logs:
        for rd in g["reads"]:
            i, _txn, _t0, t1, s, r, val = rd[:7]
            dc = rd[7] if len(rd) > 7 else 0
            if t1 is None:
                continue                     # no answer: counted as failed
            checked += 1
            if not hist.answer_ok(i, s, r, val, dc):
                window_wrong += 1
                if len(first_wrong) < 3:
                    first_wrong.append((i, _txn, _short(val))
                                       + hist.bounds(i, s, r, dc)
                                       + ((dc,) + hist.explain(i, s, r, val,
                                                               dc)
                                          if n_dcs > 1 else ()))
            elif n_dcs > 1 and not hist.causal_ok(i, r, val):
                causal_wrong += 1
                if len(first_wrong) < 3:
                    first_wrong.append((i, _txn, "causal", dc, _short(val)))
    readback_wrong = compared = 0
    for dc, part in enumerate(readback if n_dcs > 1 else [readback]):
        n, wrong = readback_diff(hist, part)
        compared += n
        readback_wrong += len(wrong)
        for i, val, want in wrong[:max(0, 6 - len(first_wrong))]:
            first_wrong.append((i, "readback" if n_dcs == 1
                                else f"readback dc{dc}", _short(val),
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
    if n_dcs > 1:
        checks["causal_wrong"] = {"value": causal_wrong, "limit": 0}
    n_back = sum(len(p) for p in (readback if n_dcs > 1 else [readback]))
    counts = {"window_answers_compared": checked,
              "readback_answers_compared": compared,
              "readback_keys_unsure": n_back - compared,
              "first_wrong": first_wrong}
    return checks, counts


def verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
