from repro.sharding.logical import (activate_mesh, constrain,
                                    current_mesh, current_rules,
                                    make_mesh, manual_shard_map,
                                    mesh_axis_sizes, rules_for,
                                    scenario_shard_map, sharding_for,
                                    spec_for)

__all__ = ["activate_mesh", "constrain", "current_mesh", "current_rules",
           "make_mesh", "manual_shard_map", "mesh_axis_sizes", "rules_for",
           "scenario_shard_map", "sharding_for", "spec_for"]
