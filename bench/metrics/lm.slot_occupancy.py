"""Mean live slots per scheduler step in the window (``Scheduler.step``'s
return value, the live slots of that step's decode)."""


def read(run):
    live = run.host.get("live_per_step")
    if not live:
        return None
    return sum(live) / len(live)
