"""decoder (serving/decoder.py): median device time of one decode
program in the trace."""
from .. import trace_reduce as tr
from ._common import program_runs


def read(facts):
    runs = program_runs(facts, "decode")
    if not runs:
        return None
    return 1e3 * tr.median([(e - s) * tr.PS for _, s, e in runs])
