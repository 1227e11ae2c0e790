from bench.harness.readers import tick_host_ms, for_family

read = for_family(tick_host_ms, "dit")
