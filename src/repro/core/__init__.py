from repro.core.adversary import (  # noqa: F401
    ATTACK_IDS,
    ATTACK_STREAM,
    ATTACKS,
    Adversary,
    apply_attack,
    attack_ids,
    make_attack_sampler,
)
from repro.core.kgt_minimax import (  # noqa: F401
    KGTState,
    diagnostics,
    init_state,
    make_round_step,
    mean_over_clients,
    point_etas,
)
from repro.core.minimax import MinimaxProblem  # noqa: F401
from repro.core.mixing import (  # noqa: F401
    MIXING_IMPLS,
    ROBUST_IMPLS,
    ROBUST_RULES,
    consensus_error,
    make_mixer,
    mix_dense,
    mix_packed,
    robust_mix_dense,
    robust_mix_packed,
    robust_mix_sparse,
)
from repro.core.packing import PackSpec, pack, pack_spec, unpack  # noqa: F401
from repro.core.objectives import (  # noqa: F401
    adversarial_problem,
    dro_problem,
    make_quadratic_data,
    quadratic_cell_problem,
    quadratic_problem,
)
from repro.core.sparse_topology import (  # noqa: F401
    SparseTopology,
    densify,
    from_dense,
    make_sparse_w_sampler,
    sparse_hierarchical,
    sparse_masked_w,
    sparse_mix,
    sparse_mixing_matrix,
)
from repro.core.stochastic_topology import (  # noqa: F401
    DENSE_MATERIALIZATION_LIMIT,
    TOPOLOGY_FAMILIES,
    bernoulli_mask,
    erdos_renyi_w,
    make_participation_sampler,
    make_w_sampler,
    masked_w,
    metropolis_weights,
    pairwise_w,
)
from repro.core.topology import mixing_matrix, spectral_gap  # noqa: F401
