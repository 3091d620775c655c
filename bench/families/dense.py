"""The dense GQA + SwiGLU family (Qwen3, Phi-3).

A family file gives the harness everything that depends on the model's
architecture, so that a configuration of another architecture comes with a
family file of its own and no harness edit:

* ``program_config(config)``: the program's ``ModelConfig`` at the file's widths;
* ``program_params(m, seed)``: the weights the program serves, on the device;
* ``score(m, seed, seqs, *, precision="f32", pad_to=256)``: the plain
  float32 reference (``precision="fp8"``: the control);
* ``prefill_flops(m, n)`` and ``decode_flops(m, context)``: the model FLOPs
  of one prompt and of one decoded token.

``m`` is the file's ``model`` and ``architecture`` groups merged
(``bench.stack.model_dict``).  The dense family only names the functions
that hold its code.
"""
from bench.flops import decode_flops, prefill_flops
from bench.reference.lm import score
from bench.stack import model_config as program_config
from bench.weights import program_params

__all__ = ["program_config", "program_params", "score", "prefill_flops", "decode_flops"]
