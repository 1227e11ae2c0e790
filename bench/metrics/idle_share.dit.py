from bench.harness.readers import idle_share, for_family

read = for_family(idle_share, "dit")
