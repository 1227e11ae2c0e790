from bench.harness.readers import device_ms_per_tick, for_family

read = for_family(device_ms_per_tick, "decode")
