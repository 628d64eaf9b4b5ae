"""model step (models/llama.py ``Llama.remat_keep_calls``): GiB of
residuals of the eligible layer calls the keep rule did NOT keep: what
``remat_replay_ms`` still rebuilds for want of room — the memory
account's ``rule.unkept_bytes`` (``Llama.keep_account``; from
shapes).  ``None`` for a model without the rule."""
from ._memory import rule_gib


def read(facts):
    return rule_gib(facts, "unkept_bytes")
