"""Core: the paper's contribution — checkerboard Ising MCMC on MXU matmuls."""
from repro.core.lattice import (  # noqa: F401
    MXU_BLOCK, Q00, Q01, Q10, Q11, BLACK_QUADS, WHITE_QUADS,
    random_lattice, cold_lattice, to_quads, from_quads, block, unblock,
    block_quads, unblock_quads,
    kernel_naive, kernel_compact, color_mask,
)
from repro.core.checkerboard import (  # noqa: F401
    acceptance, acceptance_table, nn_full, update_color_full, sweep_full,
    update_naive, nn_black, nn_white, update_color_compact, sweep_compact,
    update_color_blocked, sweep_blocked,
    quad_probs_from_full,
)
from repro.core.observables import (  # noqa: F401
    magnetization, energy_per_spin, binder_parameter, critical_temperature,
    chain_statistics,
)
from repro.core.sampler import (  # noqa: F401
    ChainConfig, run_chain, run_sweeps, init_state, measure_curve,
)
from repro.core.update_rules import (  # noqa: F401
    UpdateRule, get_rule, register_rule, rule_names,
)
from repro.core.measure import (  # noqa: F401
    Moments, init_moments, accumulate, finalize, blocked_stats,
    bond_energy_from_nn, sweep_compact_measured, sweep_blocked_measured,
)
