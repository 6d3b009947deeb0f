"""Pure-function temporal ops: GAE, V-trace, distributions, losses, noise."""

from actor_critic_algs_on_tensorflow_tpu.ops.distributions import (  # noqa: F401
    BlockReveal,
    Categorical,
    DiagGaussian,
    TanhGaussian,
)
from actor_critic_algs_on_tensorflow_tpu.ops.gae import (  # noqa: F401
    discounted_returns,
    gae_advantages,
)
from actor_critic_algs_on_tensorflow_tpu.ops.losses import (  # noqa: F401
    clipped_value_loss,
    entropy_loss,
    huber_loss,
    normalize_advantages,
    policy_gradient_loss,
    polyak_update,
    ppo_clip_loss,
    value_loss,
)
from actor_critic_algs_on_tensorflow_tpu.ops.noise import (  # noqa: F401
    OUState,
    ou_init,
    ou_reset_where,
    ou_step,
)
from actor_critic_algs_on_tensorflow_tpu.ops.normalize import (  # noqa: F401
    RunningMeanStd,
    rms_init,
    rms_normalize,
    rms_update,
)
from actor_critic_algs_on_tensorflow_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention,
)
from actor_critic_algs_on_tensorflow_tpu.ops.sequence_parallel import (  # noqa: F401
    SPVTraceOutput,
    shift_from_next,
    sp_discounted_returns,
    sp_gae_advantages,
    sp_linear_backward_scan,
    sp_vtrace,
)
from actor_critic_algs_on_tensorflow_tpu.ops.vtrace import (  # noqa: F401
    VTraceOutput,
    vtrace,
)
