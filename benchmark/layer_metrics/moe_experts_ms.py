"""Layer: experts.  Device trace, first device: time per step in the
operations under the scope `moe.experts` (the grouped products over the
held experts and the SwiGLU between them), forward, backward and
recomputed, XLA:TPU's `ragged-dot` kernels included (they carry no op path;
decoder_scopes.py), in ms."""
import decoder_scopes


def read(run):
    return decoder_scopes.scope_ms(run["trace"],
                                   (decoder_scopes.MOE_EXPERTS,), grouped=True)
