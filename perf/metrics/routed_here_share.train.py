"""Share of the router's slots that went to the experts held here,
since the program was built: the mean over the sparse layers of the
program's ``moe.routed_here_share{layer}`` gauges.  An even spread
gives held / router's experts; a lone share whose router trains on its
own experts' part of the gradient drifts above it."""
from perf import scope_readers


def read(run):
    counts = scope_readers.expert_counts(run)
    if counts is None:
        return None
    shares = counts[1]
    run.note(routed_here_share=shares)
    return sum(shares.values()) / len(shares)
