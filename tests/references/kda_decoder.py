"""The plain reference of the Kimi Delta Attention decoder (f32 jax.numpy,
no kernels and no chunked form: the equations at the head of that file with each
`assumed` item at the head of the file, the delta rule a position at a
time) is kept once, as the benchmark's reference of its configuration,
benchmark/references/kimi-linear-48b-a3b.py.  This module is that file,
loaded by path, under the name the CPU tests use."""
import importlib.util
import os

PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "benchmark", "references", "kimi-linear-48b-a3b.py")
_spec = importlib.util.spec_from_file_location("kimi_linear_reference", PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({k: v for k, v in vars(_module).items()
                  if not k.startswith("__")})
