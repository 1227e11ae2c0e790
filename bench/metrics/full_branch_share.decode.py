from bench.harness.readers import full_branch_share, for_family

read = for_family(full_branch_share, "decode")
