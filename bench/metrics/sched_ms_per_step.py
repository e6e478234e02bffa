"""Host milliseconds per window step spent in Scheduler.next_step and
Scheduler.complete_step (the driver times both calls)."""


def read(run):
    steps = run.window_steps
    return 1e3 * sum(s.sched_s for s in steps) / len(steps) if steps else None
