"""Rows the histogram passes scanned over the rows their nodes hold, from
the round program's wave log of the trees grown in the window."""


def read(run):
    need = run.facts.get("hist_rows_needed")
    return run.facts["hist_rows_scanned"] / need if need else None
