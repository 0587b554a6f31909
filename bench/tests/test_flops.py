"""The algorithmic operation counts, against counts made by hand at tiny
sizes.  Each hand count lists its terms: two operations per multiply-add
of every convolution, projection and attention product."""
import importlib.util
from pathlib import Path

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    spec = importlib.util.spec_from_file_location(name.replace("-", "_")
                                                  .replace(".", "_"),
                                                  CONFIGS / name)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY_SD = dict(latent_channels=2, latent_size=4, text_len=2, text_width=4,
               text_layers=1, text_heads=1, unet_base=4, unet_mults=[1, 2],
               unet_attn_levels=[0], unet_res_blocks=1, unet_heads=1)


def test_unet_flops_by_hand():
    sd = load("sd-v1.ref.py")
    # 4x4 latent (16 positions), base 4 (t_dim 16), level 1 at 2x2, 8 ch
    time_mlp = 2 * 4 * 16 + 2 * 16 * 16
    conv_in = 2 * 16 * 2 * 4 * 9
    res_0 = 2 * (2 * 16 * 4 * 4 * 9) + 2 * 16 * 4
    attn_0 = (2 * (2 * 16 * 4 * 4)            # proj_in, proj_out
              + 2 * 16 * 4 * 12               # q, k, v
              + 2 * 2 * 16 * 16 * 4           # scores, weighted sum
              + 2 * 16 * 4 * 4                # out
              + 2 * 16 * 4 * 4 + 2 * 2 * 4 * 8    # cross q; k, v of context
              + 2 * 2 * 16 * 2 * 4            # cross scores, sum
              + 2 * 16 * 4 * 4                # cross out
              + 2 * 2 * 16 * 4 * 16)          # MLP
    down = 2 * 4 * 4 * 4 * 9
    res_1 = 2 * 4 * 4 * 8 * 9 + 2 * 4 * 8 * 8 * 9 + 2 * 16 * 8 + 2 * 4 * 4 * 8
    mid_res = 2 * 4 * 8 * 8 * 9 * 2 + 2 * 16 * 8
    mid_attn = (2 * (2 * 4 * 8 * 8) + 2 * 4 * 8 * 24 + 2 * 2 * 4 * 4 * 8
                + 2 * 4 * 8 * 8 + 2 * 4 * 8 * 8 + 2 * 2 * 4 * 16
                + 2 * 2 * 4 * 2 * 8 + 2 * 4 * 8 * 8 + 2 * 2 * 4 * 8 * 32)
    up_1a = (2 * 4 * 16 * 8 * 9 + 2 * 4 * 8 * 8 * 9 + 2 * 16 * 8
             + 2 * 4 * 16 * 8)                # 8 + 8 skip channels in
    up_1b = (2 * 4 * 12 * 8 * 9 + 2 * 4 * 8 * 8 * 9 + 2 * 16 * 8
             + 2 * 4 * 12 * 8)                # 8 + 4
    up_conv = 2 * 16 * 8 * 8 * 9              # after the 2x upsample
    up_0a = (2 * 16 * 12 * 4 * 9 + 2 * 16 * 4 * 4 * 9 + 2 * 16 * 4
             + 2 * 16 * 12 * 4)               # 8 + 4 skip channels in
    up_0b = (2 * 16 * 8 * 4 * 9 + 2 * 16 * 4 * 4 * 9 + 2 * 16 * 4
             + 2 * 16 * 8 * 4)                # 4 + 4
    conv_out = 2 * 16 * 4 * 2 * 9
    want = (time_mlp + conv_in + res_0 + attn_0 + down + res_1
            + 2 * mid_res + mid_attn + up_1a + up_1b + up_conv
            + up_0a + up_0b + 2 * attn_0 + conv_out)
    assert want == 171264
    assert sd.unet_flops(TINY_SD) == want


def test_text_and_group_flops_by_hand():
    sd = load("sd-v1.ref.py")
    L, d = 2, 4
    per_layer = 2 * L * d * 3 * d + 2 * 2 * L * L * d + 2 * L * d * d \
        + 2 * 2 * L * d * 4 * d
    assert sd.text_flops(TINY_SD) == per_layer
    assert sd.group_flops(TINY_SD, 3, 2) == 2 * (2 * per_layer
                                                 + 2 * 3 * 171264)


def test_published_sizes():
    """The count at the cell's sizes, as PERF.md gives it."""
    import json
    sd = load("sd-v1.ref.py")
    s = json.loads((CONFIGS / "sd-v1.json").read_text())["sizes"]
    assert round(sd.unet_flops(s) / 1e12, 4) == 0.7521
