"""Loop kind `closed`: the TPC-H throughput test's shape (clause 5.3.4).
`streams` clients, each a thread that sends its next statement when the
last one's final page is in hand, cycling through its order until the
window closes. A loop kind is a file here with `schedule` and `run`; the
traffic file names it under `loop`."""

import threading
import time
from typing import List

from chipbench import stats
from chipbench import trace as trace_mod


def schedule(workload: dict, n_statements: int, per: int, rng) -> List[List[int]]:
    """Per stream, a cycle of instance indices (instance i of statement
    s is s * per + i). The pattern of statement kinds is the same for
    every seed, as the spec fixes each stream's order: slot j of a cycle
    is statement j mod n, stream k starts at slot k. The seed draws, for
    each stream and statement, which instance goes into which of its
    slots: the same work in the same pattern, in another order."""
    orders = []
    for k in range(workload["streams"]):
        slots = [rng.permutation(per) for _ in range(n_statements)]
        cycle = [
            s * per + int(slots[s][turn])
            for turn in range(per) for s in range(n_statements)
        ]
        orders.append(cycle[k % len(cycle):] + cycle[:k % len(cycle)])
    return orders


def stream(k: int, order: List[int], instances, new_client, t_end: float,
           out: list) -> None:
    import jax

    client = new_client()
    i = 0
    while time.perf_counter() < t_end:
        idx = order[i % len(order)]
        i += 1
        inst = instances[idx]
        rows, error = None, None
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(
                trace_mod.CLIENT, statement=inst.name
            ):
                rows = client.execute(inst.sql).rows
        except Exception as e:  # recorded, and counted as failed
            error = repr(e)
        out.append(stats.Sample(k, idx, t0, time.perf_counter(), rows, error))


def run(plan, new_client, seconds: float, at_close, during=None):
    """Offer the plan's load for `seconds`. Returns (samples, t0, what
    `at_close()` gave as the window closed). `during`, if given, runs in
    this thread while the streams do and gets t0."""
    samples: List[stats.Sample] = []
    t0 = time.perf_counter()
    threads = [
        threading.Thread(
            target=stream, name=f"chipbench-stream-{k}",
            args=(k, order, plan.instances, new_client, t0 + seconds, samples),
        )
        for k, order in enumerate(plan.schedule)
    ]
    for t in threads:
        t.start()
    try:
        if during is not None:
            during(t0)
        left = t0 + seconds - time.perf_counter()
        if left > 0:
            time.sleep(left)
        closed = at_close()
    finally:
        for t in threads:
            t.join()
    return samples, t0, closed
