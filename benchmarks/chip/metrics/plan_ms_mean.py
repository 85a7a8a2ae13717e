"""Scheduler's host time per step (ms): ``Scheduler.plan`` plus
``Scheduler.commit``, from the harness's spans around the engine
instance's calls, summed over the window and divided by its steps.
Moves ``itl_p95_ms``: the device waits while the host plans."""


def read(run):
    a, b = run.host_window
    steps = run.steps_in_window()
    if not steps:
        return None
    host = sum(t1 - t0 for t0, t1 in run.log.plan + run.log.commit
               if t0 >= a and t1 <= b)
    return host / len(steps) * 1e3
