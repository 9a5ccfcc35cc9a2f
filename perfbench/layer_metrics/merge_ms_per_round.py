"""Cross-chip merge: device time of the collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, with their ``-start`` and
``-done`` halves), averaged over the chips, per round (ms).  None where the
trace holds no collective, as on one chip."""

# an op's HLO text reads "%name = <type> <opcode>(<operands>)..."; an
# operand named after a collective is "%all-reduce.3", never followed by "("
COLLECTIVE = (r"(^%?|\s)(all-reduce|all-gather|reduce-scatter|"
              r"collective-permute)(-start|-done)?(\(|\.\d+$|$)")


def read(ctx):
    seconds, calls = ctx["trace"].op_seconds(COLLECTIVE)
    if not calls or not ctx["rounds"]:
        return None
    return seconds / ctx["rounds"] * 1e3
