"""Host milliseconds of one admission in the continuous scheduler, up to
the wait for its first token: the program's ``repro.sched.admit`` span
less its ``repro.sched.first_token`` child, per admission, over the traced
window.  That is the slot reset, the cache slice's take and put and the
prefill's enqueue, whose cost is the same for every prompt; the wait for
the prefill itself grows with the prompt's bucket, and the seed's order
of lengths decides which buckets a window holds, so it is left out.
Every resident waits out the whole admission for its next token.  A
program that keeps no span table leaves the metric out."""


def read(run):
    try:
        from repro.trace import recorded
    except ImportError:
        return None
    t = recorded()
    admit = t.get("repro.sched.admit")
    if not admit or not admit["calls"]:
        return None
    wait = t.get("repro.sched.first_token", {}).get("s", 0.0)
    return (admit["s"] - wait) / admit["calls"] * 1e3
