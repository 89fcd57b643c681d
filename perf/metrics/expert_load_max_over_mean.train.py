"""Token slots of the fullest held expert over the held experts' mean,
since the program was built, in the sparse layer where that ratio is
largest: 1 is an even load.  From the program's
``moe.tokens_per_expert`` counters."""
from perf import scope_readers


def read(run):
    counts = scope_readers.expert_counts(run)
    if counts is None:
        return None
    ratios = {layer: max(v) * len(v) / sum(v)
              for layer, v in counts[0].items()}
    run.note(expert_load_max_over_mean=ratios, expert_tokens=counts[0],
             expert_routed_here_share=counts[1])
    return max(ratios.values())
