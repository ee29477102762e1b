"""Checkpoint restore fallbacks are LOUD and reset to fresh state.

Unit tests for the ``repro.launch.train`` resume helpers: a param-only
checkpoint (no ``opt/`` / ``codec/`` subdir), a step mismatch, and a
topology change that reshapes the saved state must each fall back to
re-initialization with an explicit WARNING on stdout — never silently.
Silent moment/residual resets were the bug these helpers replaced: a
resumed run would quietly re-bias the gradients its ef codec exists to
de-bias.

Single-device (smoke-test contract): the fallback logic is pure
host-side control flow, so one device exercises every path.
"""

import numpy as np
import pytest

import jax

from repro import configs
from repro.core import policy, schemes
from repro.launch.mesh import make_mesh
from repro.launch.train import _restore_codec, _restore_opt
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.train import checkpoint
from repro.train.train_step import Trainer

CFG = configs.get("gemma3-1b").reduced().replace(vocab_size=64)
EF = schemes.get("zhybrid_16_8").as_policy().with_rules(
    policy.Rule("ef:bq4", dim="dp", name="zero1_grad*"), name="ef_unit")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, 1)


@pytest.fixture(scope="module")
def trainer(mesh):
    return Trainer(Model(CFG, MeshInfo.from_mesh(mesh)), mesh, scheme=EF)


@pytest.fixture(scope="module")
def state(trainer):
    return trainer.init_all(jax.random.key(0))


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def assert_tree_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# ---- missing-directory fallbacks ------------------------------------------

def test_restore_opt_no_dir_warns_and_reinits(trainer, state, mesh, capsys):
    params, ostate, _ = state
    got = _restore_opt(trainer, params, "", 3, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: no optimizer checkpoint for this step" in out
    assert_tree_equal(got, trainer.opt_init(params))


def test_restore_codec_no_dir_warns_and_reinits(trainer, mesh, capsys):
    got = _restore_codec(trainer, "", 3, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: no codec-state checkpoint for this step" in out
    assert_tree_equal(got, trainer.init_codec_state())


def test_restore_codec_stateless_scheme_is_silent(mesh, capsys):
    """No stateful codecs -> empty state, no warning (nothing was lost)."""
    tr = Trainer(Model(CFG, MeshInfo.from_mesh(mesh)), mesh,
                 scheme="baseline")
    got = _restore_codec(tr, "", 3, mesh, checkpoint)
    assert got == {}
    assert "WARNING" not in capsys.readouterr().out


# ---- step-mismatch fallbacks ----------------------------------------------

def test_restore_opt_step_mismatch_warns(trainer, state, mesh, tmp_path,
                                         capsys):
    params, ostate, _ = state
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 5, ostate)
    got = _restore_opt(trainer, params, odir, 7, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: no optimizer checkpoint for this step" in out
    assert_tree_equal(got, trainer.opt_init(params))


def test_restore_codec_step_mismatch_warns(trainer, state, mesh, tmp_path,
                                           capsys):
    cdir = str(tmp_path / "codec")
    checkpoint.save(cdir, 5, state[2])
    got = _restore_codec(trainer, cdir, 7, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: no codec-state checkpoint for this step" in out
    assert_tree_equal(got, trainer.init_codec_state())


# ---- changed-topology fallbacks -------------------------------------------

def _other_trainer(mesh):
    """Same family, different widths: the saved state cannot reshape."""
    cfg = CFG.replace(d_model=128, d_ff=256)
    return Trainer(Model(cfg, MeshInfo.from_mesh(mesh)), mesh, scheme=EF)


def test_restore_opt_changed_topology_warns(trainer, state, mesh, tmp_path,
                                            capsys):
    params, _, _ = state
    other = _other_trainer(mesh)
    op, oo, _ = other.init_all(jax.random.key(1))
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 4, oo)
    got = _restore_opt(trainer, params, odir, 4, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: optimizer state not portable to this topology" in out
    assert_tree_equal(got, trainer.opt_init(params))


def test_restore_codec_changed_topology_warns(trainer, mesh, tmp_path,
                                              capsys):
    other = _other_trainer(mesh)
    _, _, oc = other.init_all(jax.random.key(1))
    cdir = str(tmp_path / "codec")
    checkpoint.save(cdir, 4, oc)
    got = _restore_codec(trainer, cdir, 4, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: codec state not portable to this topology" in out
    assert_tree_equal(got, trainer.init_codec_state())


# ---- interleaved (vpp) topology changes ------------------------------------

def test_restore_across_changed_pp_vpp_topology(tmp_path):
    """A checkpoint saved from an interleaved (vpp=2, pp=2) plan restores
    onto a contiguous pp=4 plan and back: the v-major flatten of the
    leading (vpp, pp) dims IS round-robin chunk order == contiguous layer
    order, so the remap is a plain reshape — no permutation."""
    from repro.models.params import Pv
    vals = np.arange(2 * 2 * 2 * 3, dtype=np.float32).reshape(2, 2, 2, 3)
    checkpoint.save(str(tmp_path / "p"), 1,
                    {"g": Pv(vals, (None, "stage", None, None))})
    like = {"g": Pv(jax.ShapeDtypeStruct((4, 2, 3), np.float32),
                    ("stage", None, None))}
    out, man = checkpoint.restore(str(tmp_path / "p"), like)
    assert man["step"] == 1
    np.testing.assert_array_equal(np.asarray(out["g"].v),
                                  vals.reshape(4, 2, 3))
    assert out["g"].spec == ("stage", None, None)
    # contiguous pp=4 -> interleaved (vpp=2, pp=2)
    checkpoint.save(str(tmp_path / "q"), 2,
                    {"g": Pv(vals.reshape(4, 2, 3), ("stage", None, None))})
    like2 = {"g": Pv(jax.ShapeDtypeStruct((2, 2, 2, 3), np.float32),
                     (None, "stage", None, None))}
    out2, _ = checkpoint.restore(str(tmp_path / "q"), like2)
    np.testing.assert_array_equal(np.asarray(out2["g"].v), vals)
    assert out2["g"].spec == (None, "stage", None, None)


def test_restore_incompatible_vpp_layout_fails_loudly(tmp_path):
    """Layer-count mismatch between an interleaved save and the target
    plan raises, naming BOTH layouts — never a silent mis-permutation."""
    from repro.models.params import Pv
    vals = np.zeros((2, 2, 2, 3), dtype=np.float32)
    checkpoint.save(str(tmp_path / "p"), 1,
                    {"g": Pv(vals, (None, "stage", None, None))})
    like = {"g": Pv(jax.ShapeDtypeStruct((5, 3), np.float32),
                    (None, None))}
    with pytest.raises(ValueError) as ei:
        checkpoint.restore(str(tmp_path / "p"), like)
    assert "interleaved (vpp=2, pp=2" in str(ei.value)
    assert "flat (layers=5)" in str(ei.value)


# ---- happy paths stay quiet ------------------------------------------------

def test_restore_opt_happy_path(trainer, state, mesh, tmp_path, capsys):
    params, ostate, _ = state
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 9, ostate)
    got = _restore_opt(trainer, params, odir, 9, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "restored optimizer state at step 9" in out
    assert "WARNING" not in out
    assert_tree_equal(got, ostate)


def test_restore_codec_happy_path(trainer, state, mesh, tmp_path, capsys):
    cstate = state[2]
    cdir = str(tmp_path / "codec")
    checkpoint.save(cdir, 9, cstate)
    got = _restore_codec(trainer, cdir, 9, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "restored codec state at step 9" in out
    assert "WARNING" not in out
    assert_tree_equal(got, cstate)


# ---- the v layout tag ------------------------------------------------------

@pytest.fixture(scope="module")
def trainer8(mesh):
    from repro.train.optimizer import AdamConfig
    return Trainer(Model(CFG, MeshInfo.from_mesh(mesh)), mesh, scheme=EF,
                   opt_cfg=AdamConfig(state_bits=8))


def test_restore_opt_refuses_8bit_v_saved_before_the_tag(trainer8, mesh,
                                                         tmp_path, capsys):
    """8-bit state saved with v, not sqrt(v), and so with no tag, is not
    read as sqrt(v): the moments restart, loudly.  A tagged save comes
    back as saved."""
    params, ostate, _ = trainer8.init_all(jax.random.key(0))
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 3, ostate)
    got = _restore_opt(trainer8, params, odir, 3, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: optimizer state not portable" in out
    assert "its v is saved as 'v', this run keeps 'sqrt_v'" in out
    assert_tree_equal(got, trainer8.opt_init(params))

    checkpoint.save(odir, 4, ostate, extra={"v_layout": "sqrt_v"})
    again = _restore_opt(trainer8, params, odir, 4, mesh, checkpoint)
    assert "WARNING" not in capsys.readouterr().out
    assert_tree_equal(again, ostate)


def test_restore_opt_refuses_a_v_layout_it_cannot_read(trainer, state, mesh,
                                                       tmp_path, capsys):
    params, ostate, _ = state
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 2, ostate, extra={"v_layout": "sqrt_v"})
    got = _restore_opt(trainer, params, odir, 2, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "WARNING: optimizer state not portable to this topology" in out
    assert_tree_equal(got, trainer.opt_init(params))


# CFG's 8-bit state on one device, laid out as it always has been: m and
# sqrt(v) are bq8 wires of 616 rows of 128, the f32 master 78,848 values
STATE8_ROWS, MASTER8 = 616, 78_848


def test_8bit_state_layout_and_a_checkpoint_in_it(trainer8, mesh, tmp_path,
                                                  capsys):
    """Adam.init gives the fixed layout, and a checkpoint written in it
    (arrays of the literal shapes, not taken from this build) restores
    as saved."""
    params, ostate, _ = trainer8.init_all(jax.random.key(0))
    for k in ("m", "v"):
        assert ostate[k]["q_hi"].shape == (STATE8_ROWS, 128)
        assert ostate[k]["q_hi"].dtype == np.int8
        assert ostate[k]["q_lo"] is None
        assert ostate[k]["scale"].shape == (STATE8_ROWS, 1)
        assert ostate[k]["scale"].dtype == np.float32
    assert ostate["master"].shape == (MASTER8,)
    rng = np.random.default_rng(0)

    def wire():
        return {"q_hi": rng.integers(-127, 128, (STATE8_ROWS, 128),
                                     dtype=np.int8),
                "q_lo": None,
                "scale": rng.uniform(0.5, 2.0, (STATE8_ROWS, 1))
                .astype(np.float32)}

    saved = dict(ostate, m=wire(), v=wire(),
                 master=rng.standard_normal(MASTER8).astype(np.float32))
    odir = str(tmp_path / "opt")
    checkpoint.save(odir, 5, saved, extra={"v_layout": "sqrt_v"})
    got = _restore_opt(trainer8, params, odir, 5, mesh, checkpoint)
    out = capsys.readouterr().out
    assert "restored optimizer state at step 5" in out
    assert "WARNING" not in out
    assert_tree_equal(got, saved)
