"""The program's own names in a trace, for the readers under layer_metrics/.

The compiled train step names its inside with jax.named_scope (SCOPES: the
head of an operation's JAX op path, `tf_op`) and its host side with profiler
annotations (STEP_SPAN around one CompiledTrainStep.step, STEP_SPAN/<phase>
for the phases that tile it).  The names are literals here, as the yardstick
must not import what it measures; tests/test_train_step_scopes.py holds them
equal to the program's STEP_SCOPES and TRAIN_STEP_PHASES.  A program without
them (or no trace) reads as None, never as 0.

  scope_ms(trace, scopes, transposed)   device time per step of the first
                device's operations under the scopes; `transposed` splits
                train_step.grad into backward (JAX names what it transposes
                `transpose(jvp(...))`) and forward
  host_step_overhead_ms(trace)          the step span less its dispatch child
  collective_ms(trace, exposed)         collectives per step, whole or only
                where no other operation of the chip runs

A fused operation carries ONE op path, its root's: the split is of the
operations as named, not of the FLOPs.  Times are ms.
"""
import re
import statistics

import xplane

SCOPES = ("train_step.grad", "train_step.grad_sync", "train_step.grad_accum",
          "train_step.optimizer", "train_step.fingerprint")
GRAD, GRAD_SYNC, GRAD_ACCUM, OPTIMIZER, FINGERPRINT = SCOPES
STEP_SPAN = "tpu_mx/train_step"
PHASES = ("data_wait", "recompile", "rng_key", "dispatch",
          "optimizer_update", "record", "loss_readback")

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast")


def under(path, scopes):
    """Is one of the scopes a component of the op path?"""
    return any(f"/{scope}/" in f"/{path}/" for scope in scopes)


def scope_ms(trace, scopes, transposed=None):
    dev = xplane.first_device(trace) if trace else None
    steps, ops = xplane.stretch(dev)
    paths = [(dev["meta"].get(n, {}).get("tf_op", ""), d) for n, _, d in ops]
    if not any(under(path, SCOPES) for path, _ in paths):
        return None     # no trace, or a program that names no scope
    return sum(d for path, d in paths if under(path, scopes)
               and transposed in (None, "transpose(" in path)) \
        / len(steps) / 1e6


def spans(trace, name):
    """[start, end] of every host event of that name, in order."""
    return sorted([s, s + d] for n, s, d in trace["host"] if n == name)


def host_step_overhead_ms(trace):
    """Median over the traced steps of the STEP_SPAN's duration less the
    dispatch child inside it: the program's own Python per step (a dispatch
    behind a full device queue is back-pressure, not work)."""
    if not trace:
        return None
    dispatches = spans(trace, STEP_SPAN + "/dispatch")
    own = [end - start - sum(e - s for s, e in dispatches
                             if start <= s and e <= end)
           for start, end in spans(trace, STEP_SPAN)]
    return statistics.median(own) / 1e6 if own else None


def is_collective(name, meta):
    return bool(COLLECTIVE.search(meta.get(name, {}).get("hlo_category", "")
                                  + " " + name.split(" = ")[0]))


def collective_ms(trace, exposed=False):
    """Collectives of the first device per step of the traced stretch: the
    events of `Async XLA Ops` that are collectives (whole, from start to
    done), or where that line holds none, those of `XLA Ops` (a synchronous
    collective occupies the core's own op stream).  `exposed`: only the part
    of those intervals in which no other operation of `XLA Ops` runs."""
    dev = xplane.first_device(trace) if trace else None
    steps, ops = xplane.stretch(dev)
    if not steps:
        return None
    w0, w1 = steps[0][0], steps[-1][1]
    meta = dev["meta"]
    whole = [[s, s + d] for n, s, d in dev["lines"].get("Async XLA Ops", [])
             if w0 <= s and s + d <= w1 and is_collective(n, meta)]
    found = xplane.union(whole or [[s, s + d] for n, s, d in ops
                                   if is_collective(n, meta)])
    total = sum(e - s for s, e in found)
    if exposed:
        compute = xplane.union([s, s + d] for n, s, d in ops
                               if not is_collective(n, meta))
        total -= sum(max(0, min(e, ce) - max(s, cs))
                     for s, e in found for cs, ce in compute)
    return total / len(steps) / 1e6
