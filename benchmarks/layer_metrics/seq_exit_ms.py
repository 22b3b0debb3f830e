"""Device milliseconds per step under ``seq.pass<t>/exit``: the final norm,
the gate, the chunked head and loss of every pass, and their backward."""

from benchmarks import scopes_seq


def read(run):
    return scopes_seq.per_step_ms(run, "exit")
