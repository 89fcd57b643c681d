"""Share of the sorted slot rows (N x top_k a call, the dropless worst
case) whose chunk the sparse block worked on, since the program was
built, over the sparse layers: 1 is every chunk run, the routed share
the least it can be.  From the program's ``moe.slot_rows_run_share``
gauges; None where the program has none."""


def read(run):
    from paddle_tpu.observability import metrics
    shares = metrics.snapshot().get("moe", {}).get(
        "slot_rows_run_share", {})
    by_layer = {labels.partition("=")[2]: value
                for labels, value in shares.items()}
    # a gauge whose read failed gives None
    if not by_layer or None in by_layer.values():
        return None
    run.note(expert_rows_run_share=by_layer)
    # every layer has the same chunks a call and the same calls
    return sum(by_layer.values()) / len(by_layer)
