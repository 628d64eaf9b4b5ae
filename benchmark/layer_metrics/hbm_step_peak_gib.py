"""device: GiB the fullest chip holds at the steady step's peak, by
the program's own memory account (``step_peak_bytes``,
theanompi_tpu/obs/memory.py): without what only staging or the
warm-up held, so never over ``peak_hbm_gib``.  It differs from
``peak_hbm_gib`` only where set-up held more live buffers than a step
starts from (the two ResNet cells: a second bf16 copy of the staged
images that the first dispatch frees, 0.21 GiB and on dp4's fullest
chip 1.05); the seven decoders read the two alike to within 15 KB
(my chip runs, PR 52), and there it is the peak
``keep_account_over_peak_gib`` is taken against.  It rises with every
call the keep rule keeps (``remat_kept_gib``): lower is better at the
same kept bytes, not in itself."""
from ._memory import runtime_gib


def read(facts):
    return runtime_gib(facts, "step_peak_bytes")
