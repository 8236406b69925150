"""The step's passes by the names the program gives them, for the readers
under layer_metrics/ that ISSUE 36 brought.

tpu_mx/kernels/flash_attention.py enters each of its three `pallas_call`s
under a jax.named_scope of its own (FLASH below), inside the jitted `_fwd` /
`_bwd_call`, so an operation's op path reads
`.../attn.window/jit(_fwd)/flash.fwd/pallas_call`,
`.../jit(_bwd_call)/flash.dq/pallas_call`, `.../flash.dkv/pallas_call`,
whoever calls (GroupedQueryAttention, LatentAttention, models/bert.py): the
kernel's name is the component BEFORE `pallas_call`, which still ends the
path, so attention_scopes.KERNEL finds what it found and the rooflines that
rest on it read what they read.  The forward kernel that the backward pass
runs again under a layer's checkpoint has REMAT among its components.
tpu_mx/models/decoder.py's GatedMLP is MLP_DENSE (a dense layer; as the
expert layer's shared expert it lies under `moe.shared` too, and a reader
takes the outer name).

The names are literals, as in the three files this one is the union of
(decoder_scopes, attention_scopes, gate_scopes) and scopes.py: the yardstick
must not import what it measures; tests/test_named_passes.py holds FLASH
equal to the program's FLASH_SCOPES and OWNERS to its OWNER_SCOPES with the
step's STEP_SCOPES less `train_step.grad`.  The matcher and the step's
operations are decoder_scopes.under() and step_ops().

  scope_ms(trace, names, within=None, outside=())   device time per step
                of the first device's operations under one of `names`, of
                those only the ones also under one of `within` where given,
                and under none of `outside` (ms)
  names_kernels(trace)   does an operation lie under a name of FLASH?
  leaves(ops)   the operations in whose interval no other lies: a `while`
                or a `conditional` is on the device's line from its first
                inside operation's start to its last one's end, and what
                runs is its inside
  owned_share(trace)   share (%) of the leaves' device time that has an
                owner: under a name of OWNERS, or a grouped product
  unowned_families(trace, top=5)   the largest families (xplane.family) of
                leaves without an owner, ms a step: what to name next

A program without the names (the parent of the PR that brought them, or one
that a compile cache served the parent's executable: the cache's key leaves
debug information out, PERF.md section 7) reads as None, never as 0.
"""
import collections

import attention_scopes
import decoder_scopes
import gate_scopes
import scopes
import xplane

FLASH = ("flash.fwd", "flash.dq", "flash.dkv")
FLASH_FWD, FLASH_DQ, FLASH_DKV = FLASH
MLP_DENSE = "mlp.dense"
REMAT = "rematted_computation"
# the scopes around the kernels: head layout, rotary turn, merge and the
# backward pass's `delta` lie under them and under none of FLASH
ATTEND = (decoder_scopes.MLA_ATTEND, attention_scopes.ATTN_WINDOW,
          attention_scopes.ATTN_FULL)
PROJECT = (decoder_scopes.MLA_PROJECT, attention_scopes.ATTN_PROJECT)
# the model's own names, and with the step's (all of `train_step.grad`
# lies under that one name: it owns nothing) every name that owns a part
MODEL = decoder_scopes.SCOPES + attention_scopes.SCOPES \
    + gate_scopes.SCOPES + (MLP_DENSE,)
OWNERS = MODEL + tuple(s for s in scopes.SCOPES if s != scopes.GRAD)


def scope_ms(trace, names, within=None, outside=()):
    steps, ops = decoder_scopes.step_ops(trace)
    under = decoder_scopes.under
    took = [d for _, path, _, d in ops if under(path, names)
            and (within is None or under(path, within))
            and not under(path, outside)]
    return sum(took) / len(steps) / 1e6 if took else None


def names_kernels(trace):
    _, ops = decoder_scopes.step_ops(trace)
    return any(decoder_scopes.under(path, FLASH) for _, path, _, _ in ops)


def leaves(ops):
    """The (name, op path, start, duration) in whose interval no other
    operation of positive duration lies.  (A buffer's allocation is an
    operation of no duration at its user's start: it takes no time and
    makes no container of its user.)"""
    out, open_ = [], []     # open_: [end, operation, has an inside]
    for op in sorted((o for o in ops if o[3] > 0),
                     key=lambda o: (o[2], -o[3])):
        start, end = op[2], op[2] + op[3]
        while open_ and open_[-1][0] <= start:
            closed = open_.pop()
            if not closed[2]:
                out.append(closed[1])
        if open_ and end <= open_[-1][0]:
            open_[-1][2] = True
        open_.append([end, op, False])
    return out + [op for _, op, inside in open_ if not inside]


def is_owned(name, path):
    return decoder_scopes.under(path, OWNERS) \
        or decoder_scopes.is_grouped(name, path)


def owned_share(trace):
    _, ops = decoder_scopes.step_ops(trace)
    if not any(decoder_scopes.under(path, MODEL) for _, path, _, _ in ops):
        return None     # no trace, or a program whose model names nothing
    ran = leaves(ops)
    total = sum(d for _, _, _, d in ran)
    return 100.0 * sum(d for n, path, _, d in ran if is_owned(n, path)) \
        / total if total else None


def unowned_families(trace, top=5):
    steps, ops = decoder_scopes.step_ops(trace)
    if not steps:
        return None
    meta = xplane.first_device(trace)["meta"]
    families = collections.Counter()
    for n, path, _, d in leaves(ops):
        if not is_owned(n, path):
            families[xplane.family(n, meta)] += d
    return [[f, ns / len(steps) / 1e6] for f, ns in families.most_common(top)]
