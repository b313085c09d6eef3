"""Workflow-graph executor: ComfyUI API-format JSON → node execution over
NODE_CLASS_MAPPINGS — the L5 host layer the reference borrows from ComfyUI,
standalone here. An end-to-end graph (device chain → parallelize → empty latent
→ ksampler) runs a real sampled latent across the virtual mesh."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_parallelanything_tpu.host import WorkflowError, run_workflow


class ToyModelNode:
    """Custom node (the extension mechanism hosts allow): emits a tiny
    diffusion MODEL so graph tests don't need checkpoint files."""

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "build"

    def build(self):
        from comfyui_parallelanything_tpu.models import build_unet, sd15_config

        cfg = sd15_config(
            model_channels=32, channel_mult=(1, 2), transformer_depth=(1, 1),
            attention_levels=(0, 1), context_dim=48, num_heads=4, norm_groups=8,
            dtype=jnp.float32,
        )
        return (build_unet(cfg, jax.random.key(0), sample_shape=(1, 8, 8, 4)),)


class ToyConditioningNode:
    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "encode"

    def encode(self, seed: int = 0):
        ctx = jax.random.normal(jax.random.key(seed), (1, 6, 48))
        return ({"context": ctx},)


CUSTOM = {"ToyModel": ToyModelNode, "ToyConditioning": ToyConditioningNode}


def _chain_workflow():
    return {
        "1": {"class_type": "ParallelDevice",
              "inputs": {"device_id": "cpu:0", "percentage": 50.0}},
        "2": {"class_type": "ParallelDevice",
              "inputs": {"device_id": "cpu:1", "percentage": 50.0,
                         "previous_devices": ["1", 0]}},
    }


class TestExecutor:
    def test_chain_graph(self):
        out = run_workflow(_chain_workflow())
        chain = out["2"][0]
        assert [e["device"] for e in chain] == ["cpu:0", "cpu:1"]

    def test_literal_vs_link_distinction(self):
        # A 2-list of [str, int] is a link; scalars and other lists are literals.
        wf = _chain_workflow()
        out = run_workflow(wf)
        assert out["1"][0][0]["percentage"] == 50.0

    def test_unknown_class_raises(self):
        with pytest.raises(WorkflowError, match="unknown class_type"):
            run_workflow({"1": {"class_type": "NoSuchNode", "inputs": {}}})

    def test_pending_interrupt_stops_before_next_node(self):
        # A Cancel landing inside a non-sampler node must stop the graph at
        # the next NODE boundary, not only at sampler-step boundaries
        # (ComfyUI's per-node interrupt check).
        from comfyui_parallelanything_tpu.utils.progress import (
            Interrupted,
            clear_interrupt,
            request_interrupt,
        )

        request_interrupt()
        try:
            with pytest.raises(Interrupted, match="before node"):
                run_workflow(_chain_workflow())
        finally:
            clear_interrupt()
        # The flag was consumed: the next run proceeds normally.
        assert run_workflow(_chain_workflow())["2"][0]

    def test_unknown_link_target_raises(self):
        wf = {"1": {"class_type": "ParallelDevice",
                    "inputs": {"device_id": "cpu:0", "percentage": 50.0,
                               "previous_devices": ["99", 0]}}}
        with pytest.raises(WorkflowError, match="unknown node id"):
            run_workflow(wf)

    def test_cycle_raises(self):
        wf = {
            "1": {"class_type": "ParallelDevice",
                  "inputs": {"device_id": "cpu:0", "percentage": 50.0,
                             "previous_devices": ["2", 0]}},
            "2": {"class_type": "ParallelDevice",
                  "inputs": {"device_id": "cpu:1", "percentage": 50.0,
                             "previous_devices": ["1", 0]}},
        }
        with pytest.raises(WorkflowError, match="cycle"):
            run_workflow(wf)

    def test_out_of_range_output_raises(self):
        wf = _chain_workflow()
        wf["2"]["inputs"]["previous_devices"] = ["1", 3]
        with pytest.raises(WorkflowError, match="3 .* 1 output"):
            run_workflow(wf)

    def test_widget_list_literal_not_mistaken_for_link(self):
        # A declared widget whose literal value is a 2-list must NOT resolve as
        # a link (ComfyUI decides link-vs-literal from INPUT_TYPES; so do we).
        seen = {}

        class Sizer:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"size": ("INT", {}),
                                     "pair": ("FLOAT", {})}}

            def go(self, size, pair):
                seen["pair"] = pair
                return (size,)

        wf = {"7": {"class_type": "Sizer", "inputs": {"size": 3, "pair": [64, 0]}}}
        out = run_workflow(wf, {"Sizer": Sizer})
        assert out["7"] == (3,)
        assert seen["pair"] == [64, 0]  # stayed a literal

    def test_linked_primitive_widget_resolves(self):
        # ComfyUI's convert-widget-to-input: a declared INT widget wired from
        # another node's output arrives as [node_id, idx] and MUST resolve as a
        # link (ComfyUI's executor treats any link-shaped value as a link
        # regardless of INPUT_TYPES).
        class SeedSource:
            RETURN_TYPES = ("INT",)
            FUNCTION = "go"

            def go(self):
                return (1234,)

        class Consumer:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"seed": ("INT", {})}}

            def go(self, seed):
                return (seed,)

        wf = {
            "a": {"class_type": "SeedSource", "inputs": {}},
            "b": {"class_type": "Consumer", "inputs": {"seed": ["a", 0]}},
        }
        out = run_workflow(wf, {"SeedSource": SeedSource, "Consumer": Consumer})
        assert out["b"] == (1234,)

    def test_deep_chain_no_recursion_limit(self):
        # Link resolution is iterative: a linear chain far beyond Python's
        # recursion limit executes (no RecursionError escaping as a crash).
        class Inc:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"x": ("X", {})}}

            def go(self, x):
                return (x + 1,)

        n = 3000
        wf = {"0": {"class_type": "Inc", "inputs": {"x": -1}}}
        for i in range(1, n):
            wf[str(i)] = {"class_type": "Inc", "inputs": {"x": [str(i - 1), 0]}}
        out = run_workflow(wf, {"Inc": Inc})
        assert out[str(n - 1)] == (n - 1,)

    def test_node_error_carries_node_id(self):
        wf = {"9": {"class_type": "ParallelDevice",
                    "inputs": {"percentage": 50.0}}}  # missing device_id
        with pytest.raises(WorkflowError, match="node 9"):
            run_workflow(wf)

    def test_output_cache_skips_execution(self):
        ran = []

        class Probe:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            def go(self):
                ran.append(1)
                return ("value",)

        wf = {"1": {"class_type": "Probe", "inputs": {}}}
        seed = {"1": ("cached",)}
        out = run_workflow(wf, {"Probe": Probe}, outputs=seed)
        assert out["1"] == ("cached",) and not ran

    def test_json_file_roundtrip(self, tmp_path):
        p = tmp_path / "wf.json"
        p.write_text(json.dumps(_chain_workflow()))
        out = run_workflow(str(p))
        assert len(out["2"][0]) == 2


class TestHiddenInputs:
    def test_prompt_and_unique_id_injected(self):
        # ComfyUI executor semantics: "hidden" INPUT_TYPES entries are filled
        # by the HOST — PROMPT gets the whole workflow dict, UNIQUE_ID the
        # executing node's id.
        seen = {}

        class Probe:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {},
                        "hidden": {"prompt": "PROMPT", "uid": "UNIQUE_ID"}}

            def go(self, prompt=None, uid=None):
                seen.update(prompt=prompt, uid=uid)
                return (1,)

        wf = {"p9": {"class_type": "Probe", "inputs": {}}}
        run_workflow(wf, {"Probe": Probe})
        assert seen["uid"] == "p9"
        assert seen["prompt"]["p9"]["class_type"] == "Probe"

    def test_save_image_embeds_workflow_prompt(self, tmp_path):
        # A saved PNG carries the workflow under the 'prompt' chunk (the host
        # convention for drag-back-into-graph restoration).
        import json as _json

        from PIL import Image

        class Gen:
            RETURN_TYPES = ("IMAGE",)
            FUNCTION = "go"

            def go(self):
                return (jnp.ones((1, 4, 4, 3)) * 0.25,)

        wf = {
            "g": {"class_type": "Gen", "inputs": {}},
            "s": {"class_type": "TPUSaveImage",
                  "inputs": {"images": ["g", 0], "filename_prefix": "w",
                             "output_dir": str(tmp_path)}},
        }
        out = run_workflow(wf, {"Gen": Gen})
        (path,) = out["s"][0]
        embedded = _json.loads(Image.open(path).text["prompt"])
        assert embedded["s"]["class_type"] == "TPUSaveImage"
        assert embedded["g"]["class_type"] == "Gen"


class TestWorkflowCache:
    class _Model:
        """Teardownable output (the shape ParallelModel exposes)."""

        def __init__(self):
            self.active = True

        def cleanup(self):
            self.active = False

    def _classes(self, built):
        outer = self

        class Build:
            RETURN_TYPES = ("MODEL",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"tag": ("STRING", {})}}

            def go(self, tag):
                m = outer._Model()
                built.append((tag, m))
                return (m,)

        class Use:
            RETURN_TYPES = ("X",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"model": ("MODEL", {})}}

            def go(self, model):
                return (model,)

        return {"Build": Build, "Use": Use}

    def _wf(self, tag):
        return {
            "m": {"class_type": "Build", "inputs": {"tag": tag}},
            "u": {"class_type": "Use", "inputs": {"model": ["m", 0]}},
        }

    def test_unchanged_graph_reuses_cache(self):
        from comfyui_parallelanything_tpu.host import WorkflowCache

        built = []
        classes = self._classes(built)
        cache = WorkflowCache()
        run_workflow(self._wf("a"), classes, outputs=cache)
        run_workflow(self._wf("a"), classes, outputs=cache)
        assert len(built) == 1  # second run fully cached
        assert built[0][1].active

    def test_changed_input_evicts_and_tears_down(self):
        # Editing the model node re-executes it AND tears down the superseded
        # model — the host-side analogue of the reference's finalizer firing
        # when ComfyUI replaces a MODEL (any_device_parallel.py:1459).
        from comfyui_parallelanything_tpu.host import WorkflowCache

        built = []
        classes = self._classes(built)
        cache = WorkflowCache()
        run_workflow(self._wf("a"), classes, outputs=cache)
        out2 = run_workflow(self._wf("b"), classes, outputs=cache)
        assert [t for t, _ in built] == ["a", "b"]
        assert not built[0][1].active  # old model torn down on eviction
        assert built[1][1].active
        assert out2["u"][0] is built[1][1]  # downstream re-ran on the new model

    def test_dropped_node_evicts(self):
        from comfyui_parallelanything_tpu.host import WorkflowCache

        built = []
        classes = self._classes(built)
        cache = WorkflowCache()
        run_workflow(self._wf("a"), classes, outputs=cache)
        run_workflow({"other": {"class_type": "Build", "inputs": {"tag": "z"}}},
                     classes, outputs=cache)
        assert not built[0][1].active  # entry for removed node torn down
        assert "m" not in cache.results and "u" not in cache.results

    def test_passthrough_eviction_spares_shared_model(self):
        # A downstream node that RETURNS the model it received (the standard
        # ComfyUI MODEL pass-through) shares the object with its upstream
        # cache entry. Editing only the downstream node's literal must evict
        # and re-run it WITHOUT tearing down the still-cached upstream model.
        from comfyui_parallelanything_tpu.host import WorkflowCache

        built = []
        classes = self._classes(built)
        outer = self

        class Tag:
            RETURN_TYPES = ("MODEL",)
            FUNCTION = "go"

            @classmethod
            def INPUT_TYPES(cls):
                return {"required": {"model": ("MODEL", {}),
                                     "note": ("STRING", {})}}

            def go(self, model, note):
                return (model,)  # pass-through

        classes["Tag"] = Tag

        def wf(note):
            return {
                "m": {"class_type": "Build", "inputs": {"tag": "a"}},
                "t": {"class_type": "Tag",
                      "inputs": {"model": ["m", 0], "note": note}},
            }

        cache = WorkflowCache()
        run_workflow(wf("one"), classes, outputs=cache)
        model = built[0][1]
        run_workflow(wf("two"), classes, outputs=cache)
        assert len(built) == 1          # upstream Build stayed cached
        assert model.active             # shared model NOT torn down
        assert cache.results["t"][0] is model
        del outer

    def test_downstream_only_change_keeps_upstream_cache(self):
        from comfyui_parallelanything_tpu.host import WorkflowCache

        built = []
        classes = self._classes(built)
        cache = WorkflowCache()
        wf = self._wf("a")
        run_workflow(wf, classes, outputs=cache)
        wf2 = self._wf("a")
        wf2["u2"] = {"class_type": "Use", "inputs": {"model": ["m", 0]}}
        run_workflow(wf2, classes, outputs=cache)
        assert len(built) == 1  # upstream model untouched
        assert built[0][1].active


class TestEndToEndGraph:
    def test_full_sampling_workflow(self, cpu_devices):
        # The reference's whole value proposition as one JSON file: build a
        # chain, parallelize the model, sample a latent — every denoise step
        # rides the mesh.
        wf = {
            "dev1": {"class_type": "ParallelDevice",
                     "inputs": {"device_id": "cpu:0", "percentage": 25.0}},
            "dev2": {"class_type": "ParallelDevice",
                     "inputs": {"device_id": "cpu:1", "percentage": 25.0,
                                "previous_devices": ["dev1", 0]}},
            "dev3": {"class_type": "ParallelDevice",
                     "inputs": {"device_id": "cpu:2", "percentage": 25.0,
                                "previous_devices": ["dev2", 0]}},
            "dev4": {"class_type": "ParallelDevice",
                     "inputs": {"device_id": "cpu:3", "percentage": 25.0,
                                "previous_devices": ["dev3", 0]}},
            "model": {"class_type": "ToyModel", "inputs": {}},
            "par": {"class_type": "ParallelAnything",
                    "inputs": {"model": ["model", 0],
                               "parallel_devices": ["dev4", 0],
                               "workload_split": True,
                               "auto_vram_balance": True,
                               "purge_cache": True,
                               "purge_models": False}},
            "pos": {"class_type": "ToyConditioning", "inputs": {"seed": 1}},
            "lat": {"class_type": "TPUEmptyLatent",
                    "inputs": {"width": 64, "height": 64, "batch_size": 4}},
            "samp": {"class_type": "TPUKSampler",
                     "inputs": {"model": ["par", 0], "positive": ["pos", 0],
                                "latent": ["lat", 0], "seed": 3, "steps": 2,
                                "cfg": 1.0, "sampler_name": "euler",
                                "scheduler": "karras"}},
        }
        out = run_workflow(wf, CUSTOM)
        latent = out["samp"][0]["samples"]
        assert latent.shape == (4, 8, 8, 4)
        assert np.isfinite(np.asarray(latent)).all()
        # The MODEL that sampled is the parallel wrapper over the 4-dev chain.
        pm = out["par"][0]
        assert pm.devices == ("cpu:0", "cpu:1", "cpu:2", "cpu:3")


class TestCustomSamplingWorkflow:
    """A custom-sampling graph in API-format JSON — the node wiring exported
    FLUX workflows use (RandomNoise + KSamplerSelect + BasicScheduler +
    BasicGuider + SamplerCustomAdvanced) — executes through the host."""

    def test_custom_sampling_json_graph(self):
        wf = {
            "m": {"class_type": "ToyModel", "inputs": {}},
            "c": {"class_type": "ToyConditioning", "inputs": {"seed": 4}},
            "n": {"class_type": "TPURandomNoise", "inputs": {"noise_seed": 11}},
            "s": {"class_type": "TPUKSamplerSelect",
                  "inputs": {"sampler_name": "euler"}},
            "sig": {"class_type": "TPUBasicScheduler",
                    "inputs": {"model": ["m", 0], "scheduler": "normal",
                               "steps": 3, "denoise": 1.0}},
            "g": {"class_type": "TPUBasicGuider",
                  "inputs": {"model": ["m", 0], "conditioning": ["c", 0]}},
            "lat": {"class_type": "TPUEmptyLatent",
                    "inputs": {"width": 64, "height": 64, "batch_size": 1}},
            "out": {"class_type": "TPUSamplerCustomAdvanced",
                    "inputs": {"noise": ["n", 0], "guider": ["g", 0],
                               "sampler": ["s", 0], "sigmas": ["sig", 0],
                               "latent_image": ["lat", 0]}},
        }
        out = run_workflow(wf, CUSTOM)
        latent = out["out"][0]["samples"]
        assert latent.shape == (1, 8, 8, 4)
        assert np.isfinite(np.asarray(latent)).all()
