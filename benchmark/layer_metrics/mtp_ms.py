"""Layer: multi-token head.  Device trace, first device: time per step in
the operations under `mtp` (the module's projection, its decoder layer, its
pass of the shared head and its loss), forward, backward and recomputed, in
ms.  Its layer's grouped products carry no op path and are not in it
(decoder_scopes.py): they count in moe_experts_ms."""
import decoder_scopes


def read(run):
    return decoder_scopes.scope_ms(run["trace"], (decoder_scopes.MTP,))
