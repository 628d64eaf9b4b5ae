"""model step: model FLOP/s utilization — the operations forward and
backward need per item, from shapes (``flops.py``, recompute not
counted), times items per second per chip, over the chip's bf16 peak."""


def read(facts):
    if "flops_per_item" not in facts:
        return None
    return (facts["flops_per_item"] * facts["items_per_s_per_chip"]
            / facts["peaks"]["bf16_flops_per_s"])
