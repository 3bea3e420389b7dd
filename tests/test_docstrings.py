"""Docstring contract for the public API surfaces + the paper-map index.

The serving engine, the backend registry and the Pallas kernels are the
repo's public surfaces; their docstrings are the interface contract
(argument shapes, cache layouts, padding rules).  This is the
pydocstyle-level check CI runs so they can't rot: every public callable
must carry a docstring, the named entry points must document their Args
and Returns, and docs/paper_map.md must mention every public symbol of
``core/taylor.py`` and the Pallas kernel modules.
"""

import inspect
import pathlib

import pytest

MODULES = (
    "repro.serve",
    "repro.serve.engine",
    "repro.serve.faults",
    "repro.serve.load",
    "repro.serve.scheduler",
    "repro.serve.slots",
    "repro.serve.speculative",
    "repro.serve.state_repr",
    "repro.backends",
    "repro.backends.base",
    "repro.backends.registry",
    "repro.backends.state",
    "repro.backends.softmax",
    "repro.backends.softmax_window",
    "repro.backends.taylor",
    "repro.backends.linear_elu",
    "repro.backends.ssm",
    "repro.kernels.taylor_attention",
    "repro.kernels.taylor_attention.kernel",
    "repro.kernels.taylor_attention.kernel_bwd",
    "repro.kernels.taylor_attention.decode",
    "repro.kernels.taylor_attention.ops",
    "repro.kernels.taylor_attention.ref",
)

# Entry points whose docstrings must spell out Args: and Returns: sections
# (shapes are the contract — see docs/serving.md and docs/paper_map.md).
DOCUMENTED_SIGNATURES = {
    "repro.serve.engine": (
        "prefill", "decode_step", "decode_scan", "sample_tokens", "generate",
        "generate_loop", "prefill_chunked", "build_decode_scan",
    ),
    "repro.serve.slots": (
        "init_slot_caches", "write_slot", "clear_slot", "read_slot",
        "slot_bytes", "slot_cache_shardings", "make_sharded_slot_ops",
        "slot_health", "corrupt_slot",
    ),
    "repro.serve.faults": ("standard_trace",),
    "repro.serve.load": ("poisson_trace", "bursty_trace", "run_trace"),
    "repro.serve.speculative": ("register_proposer", "draft_available"),
    "repro.serve.state_repr": ("make_state_store", "wrap_cache_fn"),
    "repro.backends.state": (
        "quantize_leaf", "dequantize_leaf", "gather_pages", "scatter_pages",
    ),
    "repro.backends.registry": (
        "register_backend", "get_backend", "resolve_backend",
    ),
    "repro.kernels.taylor_attention.kernel": ("taylor_fwd_pallas",),
    "repro.kernels.taylor_attention.kernel_bwd": ("taylor_bwd_pallas",),
    "repro.kernels.taylor_attention.decode": ("taylor_decode_s2",),
    "repro.kernels.taylor_attention.ops": (
        "taylor_attention_kernel", "taylor_attention_kernel_trainable",
    ),
}


def _public_callables(mod):
    for name in dir(mod):
        if name.startswith("_"):
            continue
        obj = getattr(mod, name)
        if not callable(obj) or inspect.isclass(obj):
            continue
        # only enforce on callables defined in this repo
        m = getattr(obj, "__module__", "") or ""
        if m.startswith("repro"):
            yield name, obj


@pytest.mark.parametrize("modname", MODULES)
def test_module_and_public_callables_have_docstrings(modname):
    mod = __import__(modname, fromlist=["_"])
    assert (mod.__doc__ or "").strip(), f"{modname} has no module docstring"
    missing = [n for n, obj in _public_callables(mod)
               if not (inspect.getdoc(obj) or "").strip()]
    assert not missing, f"{modname}: missing docstrings: {missing}"


@pytest.mark.parametrize(
    "modname,names", sorted(DOCUMENTED_SIGNATURES.items())
)
def test_entry_points_document_args_and_returns(modname, names):
    mod = __import__(modname, fromlist=["_"])
    bad = []
    for name in names:
        doc = inspect.getdoc(getattr(mod, name)) or ""
        if "Args:" not in doc or "Returns:" not in doc:
            bad.append(name)
    assert not bad, f"{modname}: need Args:/Returns: sections: {bad}"


def test_engine_classes_documented():
    from repro.serve.faults import FaultPlan
    from repro.serve.load import (
        SLO,
        CostModel,
        LoadReport,
        Trace,
        TraceItem,
        VirtualClock,
    )
    from repro.serve.scheduler import (
        Request,
        RequestResult,
        ResiliencePolicy,
        SchedulerPolicy,
        ServeEngine,
        Status,
    )

    from repro.serve.speculative import (
        DraftProposer,
        NgramProposer,
        Order1SelfDraft,
        Speculator,
    )

    for cls in (Request, ServeEngine, RequestResult, ResiliencePolicy,
                Status, FaultPlan, SchedulerPolicy, Trace, TraceItem,
                VirtualClock, CostModel, SLO, LoadReport, DraftProposer,
                NgramProposer, Order1SelfDraft, Speculator):
        assert (inspect.getdoc(cls) or "").strip(), cls
    for meth in ("submit", "step", "run", "poll", "stats", "timings"):
        doc = inspect.getdoc(getattr(ServeEngine, meth)) or ""
        assert doc.strip(), f"ServeEngine.{meth} undocumented"
    # the proposer protocol is the extension contract — every lifecycle
    # hook must be documented
    for meth in ("propose", "on_install", "on_release", "on_rebuild"):
        doc = inspect.getdoc(getattr(DraftProposer, meth)) or ""
        assert doc.strip(), f"DraftProposer.{meth} undocumented"
    assert (inspect.getdoc(Speculator.run_rounds) or "").strip()


def test_state_repr_surface_documented():
    """The state-representation layer is public serving surface: codecs,
    the store, the allocator — classes, their public methods, and the
    quantise/page primitives in backends/state.py."""
    from repro.backends.state import (
        PagedKVCache,
        PagedMeta,
        QuantizedLeaf,
    )
    from repro.serve.state_repr import (
        DenseCodec,
        HybridCodec,
        PageAllocator,
        PagedKVCodec,
        QuantizedCodec,
        SlotStateStore,
        StateCodec,
    )

    for cls in (QuantizedLeaf, PagedKVCache, PagedMeta, StateCodec,
                DenseCodec, QuantizedCodec, PagedKVCodec, HybridCodec,
                PageAllocator, SlotStateStore):
        assert (inspect.getdoc(cls) or "").strip(), cls
    for cls, meths in (
        (SlotStateStore, ("write_slot", "read_slot", "read_dense",
                          "clear_slot", "corrupt_slot", "health",
                          "ensure_tokens", "init_caches", "live_bytes",
                          "slot_bytes")),
        (PageAllocator, ("ensure", "release", "reset")),
        (StateCodec, ("decode", "encode", "init_stored", "logical_specs")),
    ):
        for meth in meths:
            doc = inspect.getdoc(getattr(cls, meth)) or ""
            assert doc.strip(), f"{cls.__name__}.{meth} undocumented"


def test_backend_protocol_methods_documented():
    """The AttentionBackend protocol IS the backend-author contract: every
    public method (and every built-in backend class) must be documented."""
    import repro.backends as B

    missing = []
    for name, obj in inspect.getmembers(B.AttentionBackend):
        if name.startswith("_") or not callable(obj):
            continue
        if not (inspect.getdoc(obj) or "").strip():
            missing.append(f"AttentionBackend.{name}")
    for cls in (B.SoftmaxBackend, B.SoftmaxWindowBackend, B.TaylorBackend,
                B.LinearEluBackend, B.SSMBackend):
        if not (inspect.getdoc(cls) or "").strip():
            missing.append(cls.__name__)
    assert not missing, f"undocumented backend surface: {missing}"


def _module_public_symbols(mod) -> set:
    """Public names DEFINED in ``mod`` (functions, classes, upper-case
    constants) — the coverage universe for the paper map."""
    out = set()
    for name in dir(mod):
        if name.startswith("_"):
            continue
        obj = getattr(mod, name)
        if inspect.isfunction(obj) or inspect.isclass(obj):
            if getattr(obj, "__module__", "") == mod.__name__:
                out.add(name)
        elif name.isupper() and isinstance(obj, (int, float, str)):
            out.add(name)
    return out


def test_paper_map_covers_public_symbols():
    """docs/paper_map.md must mention every public symbol of
    core/taylor.py and of the Pallas kernel modules (+ the ops wrapper)
    — the acceptance bar for the paper-to-code map."""
    import repro.core.taylor as taylor
    import repro.kernels.taylor_attention.decode as decode
    import repro.kernels.taylor_attention.kernel as kernel
    import repro.kernels.taylor_attention.kernel_bwd as kernel_bwd
    import repro.kernels.taylor_attention.ops as ops

    doc = (pathlib.Path(__file__).parent.parent / "docs" / "paper_map.md"
           ).read_text()
    missing = []
    for mod in (taylor, kernel, kernel_bwd, decode, ops):
        for name in sorted(_module_public_symbols(mod)):
            if name not in doc:
                missing.append(f"{mod.__name__}.{name}")
    assert not missing, f"docs/paper_map.md does not mention: {missing}"
