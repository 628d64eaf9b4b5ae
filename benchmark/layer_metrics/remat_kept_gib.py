"""model step (models/llama.py ``Llama.remat_keep_calls``): GiB of
residuals the layer calls the keep rule kept hold on a device, and so
do not replay — the memory account's ``rule.kept_bytes``
(``Llama.keep_account``; from shapes).  ``None`` for a model without
the rule."""
from ._memory import rule_gib


def read(facts):
    return rule_gib(facts, "kept_bytes")
