"""Operation counts of one denoiser forward, pinned: the yardstick's roofline
shares rest on them. (Multiply-adds as two; attention QK^T and PV; 77 context
tokens.)"""

import run
from yardstick import layout, shapes_sd


def test_sd15_forward_at_64x64():
    c = run.load_json("configs", "sd15")
    cost = shapes_sd.unet_forward(c["unet"], 1, 64, 64, 77)
    assert cost["params"] == 859_520_964  # the published UNet, to the parameter
    assert cost["flops"] == 803_273_441_280
    step = shapes_sd.denoiser_step(c, run.load_json("traffic", "b8-512.closed"), 1)
    assert step["flops"] == 16 * cost["flops"]
    chain = shapes_sd.denoiser_step(c, run.load_json("traffic", "b8-512.closed"), 4)
    assert chain["flops"] == 4 * cost["flops"]


def test_sdxl_forward_at_128x128():
    c = run.load_json("configs", "sdxl")
    cost = shapes_sd.unet_forward(c["unet"], 1, 128, 128, 77)
    assert cost["params"] == 2_567_463_684
    assert cost["flops"] == 6_761_236_398_080
    # bytes: every parameter once at 2 B, plus activations in and out
    assert cost["bytes"] > 2 * cost["params"]


def test_checkpoint_layouts_have_the_published_parameter_counts():
    for name in ("sd15", "sdxl"):
        c = run.load_json("configs", name)
        for part in c["checkpoint"]["parts"]:
            one = dict(c, checkpoint=dict(c["checkpoint"], parts=[part]))
            n = layout.count(layout.checkpoint_layout(one))
            assert n == c["checkpoint"]["parameters"][part["sizes"]], (name, part)


