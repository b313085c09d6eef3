"""Wan2.2-T2V-A14B's whole graph against its plain reference at tiny widths on
the CPU — two ``KSamplerAdvanced`` windows on a flow table with leftover
noise — and the loader's residency rule, with the spans and counters that tell
its moves apart (``wan_twin.py`` holds the twin's files)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from twins import _counted, _moved, _rel, _residency_events, twin_files  # noqa: F401 — a fixture; benchmark/ on the path
from wan_twin import _file, fresh_residency, tiny  # noqa: F401 — fixtures
from yardstick import reference_wan, traffic


def _graph(cell, seed=5, index=0):
    sched = traffic.Schedule(cell["mix"], seed, 10)
    return traffic.fill_graph(cell["template"], cell["mix"], sched.request(index))


def test_the_whole_tiny_graph_through_the_host_equals_the_reference(
        tiny, tmp_path, fresh_residency):
    """ComfyUI's Wan2.2 two-expert graph through ``run_workflow``: two
    ``UNETLoader`` + ``LoraLoaderModelOnly`` + ``ModelSamplingSD3`` chains,
    ``CLIPLoader type=wan``, ``EmptyHunyuanLatentVideo``, two
    ``KSamplerAdvanced`` (steps 0–2 with noise and leftover noise on the
    high-noise expert, 2–4 without on the low-noise one: sigmas 1, 0.9375,
    0.8333, 0.625, 0 at shift 5), the video decode, 9 PNG files — against the
    reference's four Euler steps with the expert changed at step 2. The second
    run continues the first's state exactly: what it receives is that state
    over 1 − σ₂, as stock hands it on."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.utils import tracing
    from yardstick import client

    cell, ref_args, ref_kw = tiny
    config = cell["config_data"]
    graph = _graph(cell)
    req = reference_wan.describe(graph)
    assert (req["steps"], req["switch_step"], req["shift"], req["frames"]) == (4, 2, 5.0, 9)
    assert req["lora_strengths"] == [1.0, 1.0]
    calls = {k: _counted("pa_denoiser_calls_total", program=f"model-apply:{k}") for k in (
        "wan2.2_t2v_high_noise_14B_bf16+lora", "wan2.2_t2v_low_noise_14B_bf16+lora")}
    was_on = tracing.on()
    tracing.enable()
    try:
        res = pa.run_workflow(graph)
        events = [e for e in tracing.export()["traceEvents"] if e.get("ph") == "X"]
    finally:
        if not was_on:
            tracing.disable()
    ref = reference_wan.Reference(config, *ref_args, "float32", **ref_kw)
    want_latent = ref.latent(req)
    final = jnp.transpose(res["58"][0]["samples"], (0, 4, 1, 2, 3))
    assert final.shape == want_latent.shape == (1, 16, 3, 4, 8)
    assert _rel(final, want_latent) < 5e-3, _rel(final, want_latent)
    # the first window's output is its state at sigma_2 over (1 - sigma_2)
    sigmas = reference_wan.simple_sigmas(4, 5.0)
    assert abs(sigmas[2] - 5 / 6) < 1e-6
    handed = np.asarray(res["57"][0]["samples"]) * (1.0 - sigmas[2])
    w = ref.expert(req["experts"][1])
    ids = np.stack([ref_kw["tokenizers"]["t5"].ids(req["positive"])])
    context = ref.text_states(ids, req["clip_name"])

    def velocity(x, sigma):
        return reference_wan.wan("float32", w, config["wan"], x,
                                 jnp.full((1,), sigma, jnp.float32), context)

    continued = reference_wan.sample_euler(
        velocity, jnp.transpose(jnp.asarray(handed), (0, 4, 1, 2, 3)), sigmas[2:])
    assert _rel(final, continued) < 1e-3, _rel(final, continued)
    # the frames: 9 PNG files of 32 x 64, against the reference's float images
    paths = res["9"][0]
    assert len(paths) == 9
    rows = [0, 4, 8]
    with open(paths[0], "rb") as f:
        assert client.decode_png(f.read()).shape == (32, 64, 3)
    served = np.stack([client.decode_png(open(paths[k], "rb").read()) for k in rows])
    want = ref.images(req, rows)
    assert served.shape == want.shape == (3, 32, 64, 3)
    assert _rel(served.astype(np.float32) / 255.0, want) < 2e-2
    # 2 + 2 step spans and 2 + 2 denoise spans, each pair under its expert's name
    steps = [e for e in events if e["name"] == "step"]
    denoise = [e["args"]["program"] for e in events if e["name"] == "denoise"]
    assert len(steps) == 4
    assert denoise == ["model-apply:wan2.2_t2v_high_noise_14B_bf16+lora"] * 2 \
        + ["model-apply:wan2.2_t2v_low_noise_14B_bf16+lora"] * 2
    for k, n in calls.items():
        assert _counted("pa_denoiser_calls_total", program=f"model-apply:{k}") == n + 2
    png = [e for e in events if e["name"] == "png-encode"]
    assert len(png) == 1


def test_ksampler_advanced_hands_leftover_noise_on_as_stock_does(tiny, fresh_residency):
    """On a flow model a window that ends above σ = 0 with
    ``return_with_leftover_noise`` returns its state over (1 − σ_end); the
    next window, its noise disabled, multiplies it back. Two windows equal one
    run of all four steps."""
    from comfyui_parallelanything_tpu import models
    from comfyui_parallelanything_tpu.models import load_wan_checkpoint
    from comfyui_parallelanything_tpu.nodes import TPUKSamplerAdvanced

    cell, _, ref_kw = tiny
    model = load_wan_checkpoint(_file(cell, ref_kw, 0), models.wan_1_3b_config())
    model.sampler_prefs = {"shift": 5.0}
    ctx = {"context": jax.random.normal(jax.random.key(1), (1, 8, 32), jnp.float32),
           "pooled": None}
    latent = {"samples": jnp.zeros((1, 2, 4, 4, 16), jnp.float32)}
    common = dict(noise_seed=9, steps=4, cfg=1.0, sampler_name="euler", scheduler="simple",
                  positive=ctx, negative=ctx)
    node = TPUKSamplerAdvanced()
    (whole,) = node.sample(model, "enable", latent_image=latent, start_at_step=0,
                           end_at_step=4, return_with_leftover_noise="disable", **common)
    (first,) = node.sample(model, "enable", latent_image=latent, start_at_step=0,
                           end_at_step=2, return_with_leftover_noise="enable", **common)
    (second,) = node.sample(model, "disable", latent_image=first, start_at_step=2,
                            end_at_step=10000, return_with_leftover_noise="disable",
                            **common)
    assert _rel(second["samples"], whole["samples"]) < 1e-5


# -- the residency rule ----------------------------------------------------------------






def test_nothing_moves_when_everything_fits(tiny, tmp_path, monkeypatch):
    """A budget that holds every model and every program's temporaries: the
    rule is asked at each load and at each node that computes, and nothing
    leaves — what the five cells that were there see of it."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader

    cell, _, _ = tiny
    rule = loader.Residency(budget_bytes=1 << 40)
    monkeypatch.setattr(loader, "residency", rule)
    before = _residency_events()
    cache = pa.WorkflowCache()
    pa.run_workflow(_graph(cell), outputs=cache)
    pa.run_workflow(_graph(cell, index=1), outputs=cache)
    assert _moved(before) == {}
    assert all(e["on_chip"] for e in rule._entries.values())
    assert len(rule._entries) == 6


def test_what_nothing_computes_with_leaves_when_the_models_do_not_fit(
        tiny, tmp_path, monkeypatch):
    """The rule with a small budget passed as an argument: room for both
    patched experts, the autoencoder and the decode program's temporaries
    (which ``VideoVAE.decode`` asks for like a load's bytes), not for the
    tower and the LoRAs' unpatched copies beside them. In the first prompt
    those three — the least recently used: the tower has spoken, nothing
    samples through an unpatched copy — leave the chip, dropped, their
    loaders able to read them again. A second prompt with the same text asks
    for nothing. A prompt with a new text brings the tower back, and it
    leaves again for the decode. Every move is a ``model-residency`` span and
    a count, the gauge follows, and the frames are those of a run with no
    budget at all."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.utils import tracing
    from comfyui_parallelanything_tpu.utils.metrics import registry

    cell, _, _ = tiny
    free = loader.Residency(budget_bytes=0)
    monkeypatch.setattr(loader, "residency", free)
    plain = pa.run_workflow(_graph(cell))
    sizes = {e["model"]: e["bytes"] for e in free._entries.values()}
    tower, vae = sizes["t5"], sizes["video-vae"]
    patched = sizes["wan2.2_t2v_high_noise_14B_bf16+lora"]
    base = sizes["wan2.2_t2v_high_noise_14B_bf16"]
    (program,) = plain["39"][0]._decode_compiled.values()
    temporaries = program.memory_analysis().temp_size_in_bytes
    assert temporaries > 0
    steady = 2 * patched + vae
    budget = steady + temporaries + tower // 2
    # what the scenario below rests on: the decode is the first thing that
    # does not fit, and by then all three have to go
    assert tower + 2 * base + steady <= budget < steady + temporaries + min(tower, base)

    rule = loader.Residency(budget_bytes=budget)
    monkeypatch.setattr(loader, "residency", rule)
    before = _residency_events()
    cache = pa.WorkflowCache()
    was_on = tracing.on()
    tracing.enable()
    try:
        first = pa.run_workflow(_graph(cell), outputs=cache)
        spans = [e["args"] for e in tracing.export()["traceEvents"]
                 if e.get("name") == "model-residency"]
    finally:
        if not was_on:
            tracing.disable()
    assert _moved(before) == {
        "t5:evict": 1.0,
        "wan2.2_t2v_high_noise_14B_bf16:evict": 1.0,
        "wan2.2_t2v_low_noise_14B_bf16:evict": 1.0}
    assert [(a["model"], a["event"], a["bytes"]) for a in spans[-3:]] == [
        ("t5", "evict", tower),  # least recently used first
        ("wan2.2_t2v_high_noise_14B_bf16", "evict", base),
        ("wan2.2_t2v_low_noise_14B_bf16", "evict", base)]
    # a model is dropped, placeholders left in place: nothing is copied, and
    # it is read again when it is asked for
    encoder = first["38"][0]["encoder"]
    assert all(isinstance(leaf, loader.OffChip)
               for leaf in jax.tree.leaves(encoder.params))
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree.leaves(first["54"][0].params))
    assert registry.get("pa_params_resident_bytes",
                        {"model": "t5", "dtype": "float32"}) == 0.0
    assert rule.resident_bytes() == steady
    for k in range(9):
        assert open(first["9"][0][k], "rb").read() == open(plain["9"][0][k], "rb").read()

    # the same text, another seed: the conditioning comes from the node cache
    mark = _residency_events()
    pa.run_workflow(_graph(cell, index=1), outputs=cache)
    assert _moved(mark) == {}

    # a new text: the tower computes again, and makes way for the decode
    again = _graph(cell, index=2)
    again["6"]["inputs"]["text"] = "granite meadow lantern"
    third = pa.run_workflow(again, outputs=cache)
    assert _moved(mark) == {"t5:restore": 1.0, "t5:evict": 1.0}
    assert rule.resident_bytes() == steady
    monkeypatch.setattr(loader, "residency", free)
    want = pa.run_workflow(again)
    for k in range(9):
        assert open(third["9"][0][k], "rb").read() == open(want["9"][0][k], "rb").read()


def _made_again(name, value):
    """A model of one 64-byte tensor whose loader can make it again."""
    from comfyui_parallelanything_tpu.models.api import DiffusionModel

    def build():
        return {"w": {"kernel": jnp.full((16,), value, jnp.float32)}}

    model = DiffusionModel(apply=lambda p, x, t, c=None, **kw: x * p["w"]["kernel"][0],
                           params=build(), name=name)
    return model, build


def _on_chip(rule):
    return {e["model"] for e in rule._entries.values() if e["on_chip"]}


def test_the_rule_for_a_load_a_restore_and_a_programs_temporaries():
    """One rule, three askers, 64-byte models in a 160-byte budget: a load
    that does not fit sends the least recently used out; a model asked to
    compute again comes back and sends the next one out; a program's
    temporaries are made room for like a load's bytes; a model whose loader
    cannot make it again counts and stays."""
    from comfyui_parallelanything_tpu.models import loader

    rule = loader.Residency(budget_bytes=160)
    (a, build_a), (b, build_b), (c, build_c) = (
        _made_again(n, v) for n, v in (("a", 1.0), ("b", 2.0), ("c", 3.0)))
    rule.admit("a", a, build_a)
    rule.admit("b", b, build_b)
    assert _on_chip(rule) == {"a", "b"}
    rule.make_room(64)  # what a stored-type loader asks before it reads
    rule.admit("c", c, build_c)
    assert _on_chip(rule) == {"b", "c"} and rule.resident_bytes() == 128
    assert isinstance(a.params["w"]["kernel"], loader.OffChip)
    rule.ensure(a.params)  # a computes again: b, least recently used, leaves
    assert _on_chip(rule) == {"a", "c"}
    np.testing.assert_array_equal(np.asarray(a.params["w"]["kernel"]), np.ones(16))
    rule.ensure(a.params, beside=64)  # a's program needs 64 bytes beside it
    assert _on_chip(rule) == {"a"}
    rule.ensure(a.params, beside=1 << 20)  # more than there is: a itself stays
    assert _on_chip(rule) == {"a"}
    fixed, _ = _made_again("fixed", 4.0)
    rule.admit("fixed", fixed)  # no way to make it again
    rule.ensure(c.params)  # 64 + 64 + 64 > 160: a leaves, never ``fixed``
    assert _on_chip(rule) == {"c", "fixed"}
    rule.ensure(b.params, beside=64)
    assert _on_chip(rule) == {"b", "fixed"}


def test_an_evicted_model_comes_back_wherever_its_tensors_are_taken(monkeypatch):
    """Beyond the four compute entry points: a model the rule sent out is
    brought back where ``parallelize`` places its pytree on a chain, where
    ``quantize_model`` reads it, where a LoRA's serving factors are taken
    against it and where a sampler merges per-request factors into it — and
    whoever computes with the placeholders without asking gets an error that
    names the model and the rule."""
    import comfyui_parallelanything_tpu as pa
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.models.quantize import quantize_model
    from comfyui_parallelanything_tpu.nodes_compat import LoraLoader
    from comfyui_parallelanything_tpu.sampling.runner import run_sampler

    rule = loader.Residency(budget_bytes=100)
    monkeypatch.setattr(loader, "residency", rule)
    model, build = _made_again("m", 2.0)
    other, build_other = _made_again("other", 3.0)
    x, t = jnp.ones((8, 4)), jnp.ones((8,))

    def sent_out():
        rule.admit("m", model, build)
        rule.admit("other", other, build_other)  # 128 bytes do not fit 100
        assert isinstance(model.params["w"]["kernel"], loader.OffChip)

    sent_out()
    with pytest.raises(loader.ModelOffChip, match="m: a tensor .* off the chip"):
        np.asarray(model.params["w"]["kernel"])
    with pytest.raises(loader.ModelOffChip, match="residency.ensure"):
        model(x, t)  # a jitted call that never asked
    with pytest.raises(loader.ModelOffChip):
        model.params["w"]["kernel"].astype(jnp.bfloat16)

    # replication over a device chain
    chain = pa.DeviceChain.even([f"cpu:{i}" for i in range(4)])
    replicated = pa.parallelize(model, chain)
    np.testing.assert_allclose(np.asarray(replicated(x, t)), 2.0 * np.ones((8, 4)))
    assert _on_chip(rule) == {"m"}

    sent_out()
    assert quantize_model(model, min_size=1 << 30).n_params() == 16
    assert _on_chip(rule) == {"m"}

    sent_out()
    patched, _ = _made_again("m+lora", 2.5)
    assert LoraLoader._lane_delegate(model, patched) is None  # a bias-like delta
    assert _on_chip(rule) == {"m"}

    sent_out()
    out = run_sampler(model, jnp.ones((1, 4)), None, sampler="euler", steps=2,
                      prediction="flow", lora={})
    assert np.isfinite(np.asarray(out)).all() and _on_chip(rule) == {"m"}


def test_the_loaders_state_what_the_reference_asks_of_them():
    """``reference_wan`` looks at the program in one place: before it reads a
    tensor it asks what the loaders STATE of themselves, by name."""
    from comfyui_parallelanything_tpu.models import loader

    assert reference_wan.NEEDS <= loader.CAPABILITIES


def test_a_model_the_cache_lets_go_of_is_forgotten(fresh_residency):
    """The rule holds its models weakly: one that is let go of is gone the
    next time the rule looks, and counts for nothing."""
    import gc

    from comfyui_parallelanything_tpu.models.api import DiffusionModel

    model = DiffusionModel(apply=lambda p, x, t, c=None: x,
                           params={"w": {"kernel": jnp.ones((4, 4))}}, name="m")
    fresh_residency.budget_bytes = 100
    fresh_residency.admit("m", model, lambda: None)
    assert fresh_residency.resident_bytes() == 64
    del model
    gc.collect()
    fresh_residency.make_room(64)  # would pick it: it is gone, and nothing is moved
    assert fresh_residency.resident_bytes() == 0 and fresh_residency._entries == {}



# -- the tracer switched off ----------------------------------------------------------------


def test_with_the_tracer_off_each_new_site_is_the_one_flag_check(fresh_residency):
    """``model-residency`` around a move and the ``text-encode`` span's new
    attribute hang off ``tracing.span``: with the tracer off that is the flag
    check that returns the null span — nothing is recorded and no clock is
    read."""
    from comfyui_parallelanything_tpu.models import loader
    from comfyui_parallelanything_tpu.models.api import DiffusionModel
    from comfyui_parallelanything_tpu.utils import tracing

    assert not tracing.on()
    assert tracing.span("model-residency", cat="graph", model="m", event="evict",
                        bytes=1) is tracing._NULL
    rule = loader.Residency(budget_bytes=100)
    (a, build_a), (b, build_b) = _made_again("a", 1.0), _made_again("b", 2.0)
    n = len(tracing.export()["traceEvents"])
    rule.admit("a", a, build_a)
    rule.admit("b", b, build_b)  # 128 bytes do not fit 100: a leaves
    assert isinstance(a.params["w"]["kernel"], loader.OffChip)
    rule.ensure(a.params)  # and comes back, b leaving
    assert isinstance(a.params["w"]["kernel"], jax.Array)
    assert isinstance(b.params["w"]["kernel"], loader.OffChip)
    assert len(tracing.export()["traceEvents"]) == n
