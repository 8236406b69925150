"""Layer: attention dispatch.  Device trace, first device: time per step in
the operations under `mla.attend`, `attn.window` or `attn.full` and under
none of the three kernel names: the head layout, the rotary turn, the merge
back and the backward pass's `delta`, forward, backward and recomputed, in
ms.  With `flash_fwd_ms`, `flash_dq_ms` and `flash_dkv_ms` it adds up to
`mla_attend_ms`, or to `attn_window_ms` + `attn_full_ms`.  A program whose
kernels carry no name reports nothing (all of the scope would read as
layout)."""
import pass_scopes


def read(run):
    if not pass_scopes.names_kernels(run["trace"]):
        return None
    return pass_scopes.scope_ms(run["trace"], pass_scopes.ATTEND,
                                outside=pass_scopes.FLASH)
