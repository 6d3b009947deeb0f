"""Flax policy/value network zoo (MLP, Nature-CNN, DDPG/SAC heads) and
the table of sequence cores."""

import importlib

from actor_critic_algs_on_tensorflow_tpu.models.networks import (  # noqa: F401
    DeterministicActor,
    DiscreteActorCritic,
    FrameTransformerEncoder,
    GaussianActorCritic,
    MLPTorso,
    NatureCNN,
    QCritic,
    RecurrentActorCritic,
    SquashedGaussianActor,
    TransformerTorso,
    TwinQCritic,
)

# The sequence cores: a ``torso`` name of the recurrent trainers whose
# model owns its carry (``initialize_carry``, a step form at ``T == 1``
# and a sequence form; ``models/qwen3_next.py`` has the contract) ->
# (module, model class, config class). A new core is an entry here.
SEQUENCE_CORES = {
    "qwen3_next": ("qwen3_next", "Qwen3NextActorCritic", "Qwen3NextConfig"),
    "kimi_vl": ("kimi_vl", "KimiVLActorCritic", "KimiVLConfig"),
    "sdar": ("sdar", "SDARActorCritic", "SDARConfig"),
    "granite_hybrid": ("granite_hybrid", "GraniteHybridActorCritic",
                       "GraniteHybridConfig"),
    "xing": ("xing", "XingActorCritic", "XingConfig"),
    "lfm2": ("lfm2", "Lfm2ActorCritic", "Lfm2Config"),
}


def sequence_core(torso: str):
    """``(model class, config class)`` of the sequence core ``torso``;
    its module is imported here, on first use."""
    module, model, config = SEQUENCE_CORES[torso]
    module = importlib.import_module(f"{__name__}.{module}")
    return getattr(module, model), getattr(module, config)
