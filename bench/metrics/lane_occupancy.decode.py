from bench.harness.readers import lane_occupancy, for_family

read = for_family(lane_occupancy, "decode")
