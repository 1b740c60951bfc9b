"""Mean rows routed to each held expert per expert layer per decode step
in the window: the serving program's expert ids (its routing output, one
per call), counted over every row of each step, live or not, for the
experts this chip holds.  The router and the traffic set it (about 6 in
the chat cell: 384 choices over 64 experts, 8 held); it describes the
load each held expert's contraction serves, not the rows the program
computes for it.  A run whose program returns no routing leaves the
metric out."""


def read(run):
    rows = run.host.get("rows_per_expert")
    if not rows:
        return None
    return sum(rows) / len(rows)
