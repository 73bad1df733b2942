"""The closed loop with one caller, which waits for each reply: what a
TPC-H stream is. It issues whole cycles until `seconds` have passed; the
cycle in flight at the deadline is finished, since a rate over a window
cut inside a cycle moves with where the cut falls."""

import time


def drive(issue, traffic, seconds: float, after_cycle) -> int:
    """`issue(statement)` sends one statement and logs it;
    `after_cycle(elapsed_s)` is called between cycles. Returns the
    number of cycles."""
    t_open = time.perf_counter()
    cycles = 0
    while True:
        for st in traffic.cycle():
            issue(st)
        cycles += 1
        elapsed = time.perf_counter() - t_open
        after_cycle(elapsed)
        if elapsed >= seconds:
            return cycles
