"""Plain reference for ``cerebras-gpt-1.3b-l8.json``: the block of
``cerebras-gpt-1.3b`` at depth 8. The functions take the depth from the
configuration they are given, so this is the same code, loaded by path."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_reference_gpt2",
    os.path.join(os.path.dirname(__file__), "cerebras-gpt-1.3b.py"))
_gpt2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gpt2)

hidden, logits, loss = _gpt2.hidden, _gpt2.logits, _gpt2.loss
