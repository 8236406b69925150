"""Layer: kernels.  Device trace, first device: time per step in the
operations under `flash.dkv`, the flash attention's backward kernel that
walks a key block's query blocks (of every query head that shares the
key/value head) and writes dk and dv, whoever calls it, in ms."""
import pass_scopes


def read(run):
    return pass_scopes.scope_ms(run["trace"], (pass_scopes.FLASH_DKV,))
