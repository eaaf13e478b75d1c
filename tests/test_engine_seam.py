"""The seams of the serving layer (serve/llm.py, serve/engine.py):

* a family is one row of models/families.py: a fourth one, registered
  on the table alone, is served paged and continuous with no edit under
  ray_tpu/serve/;
* `EngineOptions` checks the options against one another where they are
  made, without building a deployment;
* the engine classes are module-level: no method reads a closure;
* a family's two files import the shared modules and each other, never
  another family's files."""

import ast
import asyncio
import inspect
import pathlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import families  # noqa: E402
from ray_tpu.serve.engine import EngineBase, LLMEngine  # noqa: E402
from ray_tpu.serve.llm import (BatchLLM, EngineOptions,  # noqa: E402
                               SpecConfig, build_llm_deployment)
from ray_tpu.serve.slo import SLOConfig  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}
_PROMPTS = [np.arange(1, 1 + n, dtype=np.int32) % 97 for n in (5, 23, 40)]


def _serve(family, prompts, **kw):
    dep = build_llm_deployment(
        family, "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, prefill_bucket=16, max_new_tokens=6,
        config_overrides=_OVR, **kw)

    async def main():
        engine = dep.func_or_class()
        try:
            return await asyncio.wait_for(
                asyncio.gather(*[engine(p) for p in prompts]), 300)
        finally:
            engine.shutdown_engine()

    return dep, asyncio.run(main())


def test_a_fourth_family_is_one_row(monkeypatch):
    """gpt2's programs under another name, registered on the table and
    nowhere else, answer token for token like "gpt2"."""
    kind, load = families.FAMILIES["gpt2"]
    monkeypatch.setitem(families.FAMILIES, "toy", (kind, load))
    dep, toy = _serve("toy", _PROMPTS)
    _, ref = _serve("gpt2", _PROMPTS)
    assert dep.name == "llm_toy_nano"
    assert families.family("toy").name == "toy"
    for got, want, prompt in zip(toy, ref, _PROMPTS):
        assert len(got) == len(prompt) + 6
        np.testing.assert_array_equal(got, want)
    # and the table's new row is a draft the spec check admits
    assert SpecConfig(draft="toy:nano").draft == "toy:nano"


def test_an_unknown_family_is_refused():
    with pytest.raises(ValueError, match="unknown LM family 'toy'"):
        EngineOptions(family="toy")
    with pytest.raises(ValueError, match="spec draft must be"):
        SpecConfig(draft="toy:nano")


_PAGED = dict(scheduler="continuous", kv_layout="paged")


@pytest.mark.parametrize("options, text", [
    (dict(scheduler="fifo"), "unknown scheduler 'fifo'"),
    (dict(kv_layout="ragged"), "unknown kv_layout 'ragged'"),
    (dict(kv_layout="paged"),
     "kv_layout='paged' requires scheduler='continuous'"),
    (dict(scheduler="continuous", prefill_chunk_tokens=16),
     "prefill_chunk_tokens requires kv_layout='paged'"),
    (dict(prefill_chunk_tokens=24, **_PAGED),
     "prefill_chunk_tokens=24 must be a positive multiple of "
     "kv_block_size=16"),
    (dict(scheduler="continuous", kv_host_tier_bytes=1 << 20),
     "kv_host_tier_bytes requires kv_layout='paged'"),
    (dict(kv_host_tier_bytes=0, **_PAGED),
     "kv_host_tier_bytes=0 must be a positive byte budget"),
    (dict(role="draft"), "unknown role 'draft'"),
    (dict(role="prefill"),
     "role='prefill' requires scheduler='continuous'"),
    (dict(role="decode", scheduler="continuous"),
     "role='decode' requires kv_layout='paged'"),
    (dict(handoff_staged=True),
     "handoff_staged only applies to split roles"),
    (dict(mesh=object()),
     "mesh-sharded serving requires scheduler='continuous'"),
    (dict(spec_decode="ngram"),
     "spec_decode must be a SpecConfig, got str"),
    (dict(spec_decode=SpecConfig()),
     "spec_decode requires scheduler='continuous'"),
    (dict(slo="tight"), "slo must be a serve.slo.SLOConfig, got str"),
    (dict(slo=SLOConfig()), "slo requires scheduler='continuous'"),
    (dict(temperature=-1.0), "temperature must be >= 0"),
    (dict(stop_sequences=[[]]), "empty stop sequence"),
    (dict(family="jamba", spec_decode=SpecConfig(), **_PAGED),
     "which spec_decode cannot carry yet: refused"),
    (dict(family="jamba", kv_host_tier_bytes=1 << 20, **_PAGED),
     "which kv_host_tier_bytes cannot carry yet: refused"),
    (dict(family="jamba", role="prefill", **_PAGED),
     "which role='prefill' cannot carry yet: refused"),
    (dict(family="jamba", mesh=object(), **_PAGED),
     "which mesh cannot carry yet: refused"),
])
def test_options_are_checked_where_they_are_made(options, text):
    """Each cross-check of the options, without a deployment: the error
    `build_llm_deployment` raises for the same options."""
    with pytest.raises(ValueError) as direct:
        EngineOptions(**options)
    assert text in str(direct.value)
    with pytest.raises(ValueError) as built:
        build_llm_deployment(**options)
    assert str(built.value) == str(direct.value)


def test_options_are_the_builders_parameters():
    """Same names, same order, same defaults; and what an engine reads
    beside them is derived once."""
    params = inspect.signature(build_llm_deployment).parameters
    fields = EngineOptions.__dataclass_fields__
    assert list(params) == list(fields) and len(fields) == 28
    assert all(params[n].default == fields[n].default for n in fields)
    opt = EngineOptions(temperature=0.5, top_k=3,
                        stop_sequences=[[1, 2], np.asarray([3])])
    assert opt.stop_seqs == ((1, 2), (3,))
    assert (opt.default_sp.temperature, opt.default_sp.top_k) == (0.5, 3)
    with pytest.raises(AttributeError):
        opt.max_slots = 8


@pytest.mark.parametrize("scheduler, cls", [("continuous", LLMEngine),
                                            ("batch", BatchLLM)])
def test_the_engine_is_a_module_level_class(scheduler, cls):
    """No method takes a name from an enclosing function, and the
    deployed class is the scheduler's with the options bound."""
    for klass in (EngineBase, cls):
        closures = {name: m.__code__.co_freevars
                    for name, m in vars(klass).items()
                    if hasattr(m, "__code__") and m.__code__.co_freevars}
        assert closures == {}
    deployed = build_llm_deployment(scheduler=scheduler,
                                    max_slots=3).func_or_class
    assert issubclass(deployed, cls) and deployed.opt.max_slots == 3
    assert cls.opt is None
    assert inspect.iscoroutinefunction(deployed.__call__)
    assert getattr(inspect.getmodule(cls), cls.__name__) is cls


#: the one family that still builds on another's files, until latent
#: attention has a module of its own (ROADMAP.md C2c, "latent attention
#: to models/mla.py"): GLM-5's config subclasses Kimi-K2's and its
#: programs call Kimi-K2's attention
_BORROWS = {("glm_dsa", "kimi_k2")}


def test_a_familys_files_name_no_other_family():
    """An import under ray_tpu/models/ that reaches a family's files
    (`<family>.py`, `<family>_decode.py`) comes from that family's own
    pair, from the table of families, or from the package's
    ``__init__``: what two families call lives in a module named for
    what it is."""
    root = pathlib.Path(families.__file__).parent
    owner = {stem: name for name in families.FAMILIES
             for stem in (name, name + "_decode")}
    found = set()
    for path in sorted(root.glob("*.py")):
        if path.stem in ("families", "__init__"):
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.ImportFrom) or not (
                    node.module or "").startswith("ray_tpu.models"):
                continue
            stems = [node.module.rsplit(".", 1)[-1]] \
                + [alias.name for alias in node.names]
            found |= {(owner.get(path.stem, path.stem), owner[stem])
                      for stem in stems if stem in owner}
    crossing = {(me, other) for me, other in found if me != other}
    assert crossing == _BORROWS, sorted(crossing ^ _BORROWS)
