"""The decoder blocks' own names in a trace, for the readers under
layer_metrics/ that ISSUE 27 brought.

tpu_mx/models/decoder.py and tpu_mx/parallel/moe.py name their parts with
jax.named_scope (SCOPES below, as literals: the yardstick must not import
what it measures; tests/test_latent_moe_decoder.py holds them equal to the
program's DECODER_SCOPES).  They are entered INSIDE the differentiated
function, so JAX writes them into an operation's op path (`tf_op`) wrapped:
`.../jvp(mla.attend)/...` forward, `.../transpose(jvp(mla.attend))/...`
backward, bare (`.../jvp(mtp)/mla.attend/...`) under an outer scope, and
under `checkpoint/` again, with `rematted_computation/` where the backward
pass recomputes a layer's inside.  scopes.under() matches bare components
only, hence the matcher here.

XLA:TPU turns `jax.lax.ragged_dot`, the expert layer's grouped product, into
a kernel of its own whose operation is named `ragged-dot…` and carries NO op
path: GROUPED finds it by name.  It belongs to `moe.experts`, which is the
only caller; its forward, recomputed and backward products cannot be told
apart by name, and nothing here tries.

  scope_ms(trace, scopes, grouped=False)   device time per step of the first
                device's operations under the scopes (ms), with the grouped
                products where asked
  census(run)   what the program's expert layers counted in the last step
                run (tpu_mx.parallel.moe.load_census on the net that the
                configuration's make_step() left in run["cfg"]["live"], a
                reader being handed neither net nor step), None where the
                program has no such layer

A program without these scopes (or no trace) reads as None, never as 0.
A fused operation carries ONE op path, its root's: the split is of the
operations as named.
"""
import re

import xplane

SCOPES = ("mla.project", "mla.attend", "moe.route", "moe.experts",
          "moe.shared", "moe.combine", "mtp", "lm_head")
(MLA_PROJECT, MLA_ATTEND, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, MOE_COMBINE,
 MTP, LM_HEAD) = SCOPES
GROUPED = re.compile(r"ragged-dot")
_WRAP = re.compile(r"transpose\(|jvp\(|\)")


def under(path, scopes):
    """Is one of the scopes a component of the op path, bare or wrapped in
    jvp( ) and transpose( )?"""
    return any(_WRAP.sub("", part) in scopes for part in path.split("/"))


def is_backward(path):
    return "transpose(" in path


def is_grouped(name, path):
    return bool(GROUPED.search(name.split(" = ")[0]) or GROUPED.search(path))


def step_ops(trace):
    """(steps, [(name, op path, start, duration)]) of the first device's
    traced stretch; ([], []) where there is none."""
    dev = xplane.first_device(trace) if trace else None
    steps, ops = xplane.stretch(dev)
    return steps, [(n, dev["meta"].get(n, {}).get("tf_op", ""), s, d)
                   for n, s, d in ops]


def names_decoder(ops):
    return any(under(path, SCOPES) for _, path, _, _ in ops)


def scope_ms(trace, scopes, grouped=False):
    steps, ops = step_ops(trace)
    if not names_decoder(ops):
        return None     # no trace, or a program that names no such scope
    return sum(d for n, path, _, d in ops if under(path, scopes)
               or (grouped and is_grouped(n, path))) / len(steps) / 1e6


def census(run):
    live = run["cfg"].get("live")
    try:
        from tpu_mx.parallel.moe import load_census
    except ImportError:     # a program from before the dropless layer
        return None
    if not live:
        return None
    # the step holds the values it trains; the net gets them back
    live["step"].sync_to_net()
    return load_census(live["net"]) or None
