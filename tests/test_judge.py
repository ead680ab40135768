"""The judge's own cluster-free checks (``benchmark/tests``), run with
tier-1 so that a PR which breaks the yardstick's arithmetic, its data
files or the command's refusal without a chip sees it here: trace
reduction, FLOPs and grouped-matmul work against hand-worked numbers,
traffic from the seed, the burst-edged rate, the data files against
``BENCHMARK.json``.

They are imported, not copied: ``benchmark/`` is the yardstick and only a
``benchmark`` PR edits it.  The end-to-end cells at tiny size stay out
(they start clusters; ``python -m pytest benchmark/tests -q`` runs them).
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

pytest.register_assert_rewrite(
    "benchmark.tests.test_benchmark", "benchmark.tests.test_olmoe_cell",
    "benchmark.tests.test_mistral_small_4_cell", "benchmark.tests.test_nemotron_3_nano_cell",
    "benchmark.tests.test_granite_4_0_h_small_cell", "benchmark.tests.test_mellum2_cell",
    "benchmark.tests.test_jamba2_cell", "benchmark.tests.test_zaya1_cell",
    "benchmark.tests.test_glm_5_cell",
)

from benchmark.tests.test_benchmark import (  # noqa: E402,F401
    test_closed_loop_rate_is_cut_at_bursts,
    test_data_files_load_and_agree_with_benchmark_json,
    test_flops_against_hand_worked_numbers,
    test_one_entry_for_each_thing_measured_and_room_for_the_next_cells,
    test_operation_names_are_cut_to_instruction_and_target,
    test_recorded_chip_trace_reduces,
    test_run_refuses_without_a_chip,
    test_spread_reads_a_set_as_the_check_does,
    test_stats_delta_arithmetic,
    test_the_fold_dropped_nothing_a_cell_read,
    test_trace_readers_on_the_reduced_trace,
    test_trace_reduction_busy_union_time_by_name_and_gaps,
    test_traffic_from_the_seed,
)
from benchmark.tests.test_olmoe_cell import (  # noqa: E402,F401
    test_every_seed_takes_the_pool_from_the_head_of_the_same_order,
    test_grouped_matmul_work_and_roofline_share_by_hand,
    test_runner_fails_at_once_where_the_program_has_no_such_family,
)
from benchmark.tests.test_mistral_small_4_cell import (  # noqa: E402,F401
    test_decode_kernel_and_held_experts_work_and_roofline_shares_by_hand,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
)
from benchmark.tests.test_mistral_small_4_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_mistral_runner_fails_at_once_where_the_program_has_no_such_family,
)
from benchmark.tests.test_nemotron_3_nano_cell import (  # noqa: E402,F401
    test_decode_kernels_and_held_experts_work_and_roofline_shares_by_hand,
    test_the_stated_cache_is_three_paged_layers_and_two_arrays_a_mamba_layer,
)
from benchmark.tests.test_nemotron_3_nano_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_nemotron_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_nemotron_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_nemotron_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
)
from benchmark.tests.test_granite_4_0_h_small_cell import (  # noqa: E402,F401
    test_decode_kernels_held_experts_and_chunk_work_and_their_shares_by_hand,
    test_the_cut_s_arithmetic_reckoned_again,
    test_the_stated_cache_is_one_paged_layer_and_two_arrays_a_mamba_layer,
)
from benchmark.tests.test_granite_4_0_h_small_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_granite_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_granite_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_granite_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
)
from benchmark.tests.test_mellum2_cell import (  # noqa: E402,F401
    test_decode_kernel_experts_and_chunk_work_and_their_shares_by_hand,
    test_the_stated_cache_is_three_paged_layers_and_two_rings_a_lane,
)
from benchmark.tests.test_mellum2_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_mellum_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_mellum_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_mellum_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
    test_the_cut_s_arithmetic_reckoned_again as test_the_mellum_cut_s_arithmetic_reckoned_again,
)
from benchmark.tests.test_jamba2_cell import (  # noqa: E402,F401
    test_the_configuration_is_the_catalog_s_with_nothing_reduced,
    test_the_stated_cache_is_two_paged_layers_of_one_head_and_two_arrays_a_mamba_layer,
    test_the_two_scan_kernels_the_grouped_query_kernel_and_a_chunk_s_work_by_hand,
    test_the_whole_model_s_arithmetic_reckoned_again,
    test_wrong_reference_swaps_the_mixers_of_the_layers_it_moves,
)
from benchmark.tests.test_jamba2_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_jamba_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_jamba_cell_s_metrics_are_the_entries_of_benchmark_json,
)
from benchmark.tests.test_zaya1_cell import (  # noqa: E402,F401
    test_the_stated_cache_is_twenty_paged_layers_and_a_tail_a_lane_a_layer,
)
from benchmark.tests.test_zaya1_cell import (  # noqa: E402,F401
    test_decode_kernel_experts_and_chunk_work_and_their_shares_by_hand as test_the_zaya_decode_kernel_experts_and_chunk_work_and_their_shares_by_hand,
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_zaya_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_zaya_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_zaya_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
    test_the_cut_s_arithmetic_reckoned_again as test_the_zaya_cut_s_arithmetic_reckoned_again,
)
from benchmark.tests.test_glm_5_cell import (  # noqa: E402,F401
    test_the_two_decode_kernels_work_and_roofline_shares_by_hand,
)
from benchmark.tests.test_glm_5_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_glm_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_glm_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_glm_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
    test_the_cut_s_arithmetic_reckoned_again as test_the_glm_cut_s_arithmetic_reckoned_again,
)
from benchmark.tests.test_kimi_linear_cell import (  # noqa: E402,F401
    test_the_two_decode_kernels_and_the_chunk_form_s_work_by_hand,
)
from benchmark.tests.test_kimi_linear_cell import (  # noqa: E402,F401
    test_runner_fails_at_once_where_the_program_has_no_such_family as test_kimi_runner_fails_at_once_where_the_program_has_no_such_family,
    test_the_cell_s_metrics_are_the_entries_of_benchmark_json as test_the_kimi_cell_s_metrics_are_the_entries_of_benchmark_json,
    test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced as test_the_kimi_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced,
    test_the_cut_s_arithmetic_reckoned_again as test_the_kimi_cut_s_arithmetic_reckoned_again,
)
