"""How a configuration's outputs are held against its reference.

One module a kind, each with ``compare(ctx) -> {"ok", "why", ...numbers}``.
A configuration's file names its kind under ``"comparison"`` (default
``logprob_walk``) and its reference module under ``"reference"``
(default ``reference``: ``references/<name>.py`` where that file exists,
else the module of that name beside ``run_cell.py``). ``resolve`` finds
both before the engine is built, so a wrong name costs no chip time.

``ctx``: ``base`` and ``model`` (the server's URL and model name),
``engine`` (the engine object: ``params``, ``mesh``, ``cfg``), ``params``
(the weights the reference computes with: the engine's own arrays, in
place), ``hf`` (the configuration's published keys), ``tokenizer_file``,
``seed`` and ``reference`` (the module).
"""
import importlib
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DEFAULT_KIND = "logprob_walk"
DEFAULT_REFERENCE = "reference"
_NAME = re.compile(r"^[A-Za-z0-9_]{1,64}$")


def resolve(config: dict):
    """(the comparison's module, the reference's module) a configuration
    names; ``ValueError`` where a name finds no file."""
    kind = config.get("comparison", DEFAULT_KIND)
    ref = config.get("reference", DEFAULT_REFERENCE)
    for what, name in (("comparison", kind), ("reference", ref)):
        if not isinstance(name, str) or not _NAME.match(name):
            raise ValueError(f"{what} {name!r} is not a module's name")
    if not os.path.exists(os.path.join(HERE, f"{kind}.py")):
        raise ValueError(f"comparison {kind!r}: no benchmark/comparisons/{kind}.py")
    if os.path.exists(os.path.join(BENCH, "references", f"{ref}.py")):
        ref_module = f"references.{ref}"
    elif os.path.exists(os.path.join(BENCH, f"{ref}.py")):
        ref_module = ref
    else:
        raise ValueError(
            f"reference {ref!r}: no benchmark/references/{ref}.py and no benchmark/{ref}.py"
        )
    comparison = importlib.import_module(f"comparisons.{kind}")
    if not callable(getattr(comparison, "compare", None)):
        raise ValueError(f"comparisons/{kind}.py has no compare(ctx)")
    return comparison, importlib.import_module(ref_module)
