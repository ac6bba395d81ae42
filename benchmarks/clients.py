"""One way to open a connection, whatever stands behind the port: the
program's wire client, or the reference's.  `Faulty` is the test harness's
hook: it breaks the timed path underneath the comparison."""

from __future__ import annotations

FAULTS = ("alter_answer", "drop_update")


def connect(kind: str, host: str, port: int, timeout: float = 60.0,
            fault: str | None = None, every: int = 40):
    if kind == "wire":
        from antidote_tpu.proto.client import AntidoteClient
        c = AntidoteClient(host, port, timeout=timeout)
    elif kind == "reference":
        from benchmarks.reference.server import RefClient
        c = RefClient(host, port, timeout=timeout)
    else:
        raise ValueError(f"unknown client kind {kind!r}")
    return Faulty(c, fault, every) if fault else c


def refused(exc: BaseException) -> bool:
    """True for a typed reply of the server (busy, shed, abort, deadline):
    the request failed and the connection still stands."""
    return type(exc).__name__.startswith("Remote")


class Faulty:
    """Wraps a client and plants one fault where answers are produced."""

    def __init__(self, inner, fault: str, every: int):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self._c, self._fault, self._every = inner, fault, every
        self._n = 0

    def _due(self) -> bool:
        self._n += 1
        return self._n % self._every == 0

    def update_objects(self, updates):
        if self._fault == "drop_update" and self._due():
            return [0]                 # acknowledged, the state unchanged
        return self._c.update_objects(updates)

    def read_objects(self, objects):
        values, clock = self._c.read_objects(objects)
        if self._fault == "alter_answer" and self._due():
            v = values[0]
            values[0] = v + 1 if isinstance(v, int) else list(v)[1:]
        return values, clock

    def start_transaction(self):
        return self._c.start_transaction()

    def node_status(self):
        return self._c.node_status()

    def close(self):
        self._c.close()
