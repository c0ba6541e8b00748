"""Bytes the forward keeps for the backward, first trace: the sum over
the tape's pullbacks of the arrays they hold, each once, the step's own
inputs left out (`train_step.residuals`; whole-program shapes under
GSPMD). The note lists every key ("scope:taped op") largest first with its
share of the compiler's `temp_bytes` over all devices, the inputs held
(`state_bytes`), and the `train_step.kept` events: name x calls x bytes
of one call."""
LAYER = "compiled step"
UNIT = "bytes"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import step_memory
    if run.get("kind") != "train":
        return None
    found = step_memory.residuals()
    if found is None:
        return None
    total, rows = found
    mem = step_memory.memory()
    temp = mem["temp_bytes"] * mem["devices"] if mem else None

    def share(n):
        return f" ({100.0 * n / temp:.1f}% of temp)" if temp else ""

    kept = "; ".join(f"{name} x {calls} x {nbytes}" for name, (calls, nbytes)
                     in step_memory.kept().items())
    note = (", ".join(f"{scope}={n}{share(n)} in {arrays}"
                      for scope, n, arrays in rows)
            + f" | total{share(total['bytes'])} arrays={total['arrays']}"
              f" state_bytes={total['state_bytes']}"
              f" shapes={total['shapes']} walk_s={total['dur_s']:.4f}"
            + (f" temp_bytes={mem['temp_bytes']} x {mem['devices']}"
               if mem else "")
            + f" | kept: {kept or 'none'}")
    return total["bytes"], note
