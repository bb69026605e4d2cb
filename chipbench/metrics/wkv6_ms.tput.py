"""Device milliseconds of the Pallas ``wkv6`` kernel per run of the engine's
decode program, in the traced stretch (moves the gap between tokens).

Only the decode's calls count.  The engine prefills one request at a time
(batch 1) and decodes every slot at once, so the decode's calls are those
whose batch, the first dimension of ``r`` in the call's signature, is the
largest among the traced ``wkv6`` calls and above 1.  None where the trace
holds no such call or no decode run."""
from chipbench.derive import DECODE, program_time


def _batch(sig: str) -> int:
    operands, _ = eval(sig)
    return operands[0][1][0]


def read(run):
    _, runs = program_time(run, DECODE)
    devs = run.trace["devices"] if run.trace else []
    per_dev = []
    for dev in devs:
        by_sig = dev["kernels"].get("wkv6", [0.0, 0, {}])[2]
        slots = max((_batch(sig) for sig in by_sig), default=0)
        if slots > 1:
            per_dev.append(sum(s for sig, (s, _) in by_sig.items()
                               if _batch(sig) == slots))
    if not runs or not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / runs * 1e3
