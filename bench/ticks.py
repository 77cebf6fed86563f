"""What a traffic driver records of each service tick, for the readers in
``bench/metrics/`` and the comparison after the window."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Tick:
    index: int
    versions: dict  # relation -> data version this tick read
    clients: list  # the client of each request
    submitted: list  # perf_counter at each request's submit
    done: list  # perf_counter when each request's outputs were ready
    ok: list  # whether the service completed each request
    jobs: list  # per job: {"backend", "recv_fwd", "hits", "key_width"}
    n_jobs: int = 0
    bytes_shuffled: int = 0
    requests: list = field(default_factory=list)
    outputs: list = field(default_factory=list)  # host rows, kept ticks only

    @property
    def latencies(self) -> list[float]:
        return [d - s for d, s, ok in zip(self.done, self.submitted, self.ok) if ok]


def job_summary(record) -> dict:
    stats = record.stats
    sjs = getattr(record.job, "sjs", ())
    return {
        "backend": record.backend,
        "recv_fwd": int(stats.get("recv_fwd", 0)),
        "hits": int(stats.get("hits", 0)),
        "key_width": max((len(sj.key_vars) for sj in sjs), default=0),
    }


def serve(server, index: int, versions: dict, clients: list, clock) -> Tick:
    """Submit one request for each of ``clients``, tick the service once,
    and wait for every request's outputs."""
    submitted, reqs = [], []
    for c in clients:
        submitted.append(clock())
        reqs.append(server.submit(c))
    server.tick()
    done, ok = [], []
    for r in reqs:
        ok.append(server.wait(r))
        done.append(clock())
    report = server.last_report()
    records = report.records if report is not None else []
    return Tick(
        index=index, versions=versions, clients=list(clients),
        submitted=submitted, done=done, ok=ok,
        jobs=[job_summary(r) for r in records], n_jobs=len(records),
        bytes_shuffled=report.bytes_shuffled() if report is not None else 0,
        requests=reqs,
    )
