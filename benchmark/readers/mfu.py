"""Model FLOP/s utilization of a training cell, in percent: tokens per
second times the operations a token's forward and backward pass need
(``costs.train_flops_per_token``: 6 N + 12 L h s, recomputation not
counted) over chips times the bf16 peak in ``peaks.json``. An
end-to-end utilization, not a kernel's roofline share."""

from benchmark import costs


def read(params, run):
    rate = run.end_to_end.get("train_tok_s")
    if rate is None:
        return None
    per_token = costs.train_flops_per_token(run.config,
                                            run.counters["seq_len"])
    return 100.0 * rate * per_token / (
        run.counters["chips"] * run.peaks["bf16_flops_per_s"])
