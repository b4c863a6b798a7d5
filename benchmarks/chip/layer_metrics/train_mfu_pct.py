"""Model FLOP/s utilisation: the operations a row requires (the consumer
adaptor's ``flops_per_row``; recomputation and surplus logits not counted)
times rows per second, over chips times the table's bf16 peak."""


def read(sample):
    if "flops_per_row" not in sample:
        return None
    achieved = sample["flops_per_row"] * sample["rows"] / sample["window_s"]
    return 100.0 * achieved / (sample["chips"] * sample["peaks"]["bf16_flops"])
