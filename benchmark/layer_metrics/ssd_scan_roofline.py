"""kernels (ops/ssd.py ``ssd_scan``): the least time the chip could
take for a step's state-space scans — every mamba layer's forward and
backward, each call's operations and bytes from its shapes
(``flops_ssd.ssd_call_need`` at the configuration's
``kernels.ssd_scan.shape``) against the peaks table — over the device
time of EVERY instruction under the configuration's
``kernels.ssd_scan.scope``, fusion or custom call, forward, replay
and backward.  The replay's time counts, its work is no need.  The
scan has no kernel of its own name: whatever implements the scope is
read.  ``None`` for a program without the scope (every program from
before PR 47)."""
from .. import flops, flops_ssd
from ._scopes import scope_seconds


def read(facts):
    config = facts["cell"]["config"]
    spec = config.get("kernels", {}).get("ssd_scan")
    if not spec:
        return None
    got = scope_seconds(facts, spec["scope"])
    if got is None:
        return None
    took, _, steps = got
    layers = list(
        config["layer_types"][:config["num_hidden_layers"]]
    ).count("mamba")
    least = sum(
        flops.least_seconds(
            *flops_ssd.ssd_call_need(kind, **spec["shape"]), facts["peaks"]
        )[0]
        for kind in ("fwd", "bwd")
    )
    return 100.0 * steps * layers * least / took
