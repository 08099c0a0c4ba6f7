CREATE TABLE nexmark WITH (
  connector = 'nexmark', event_rate = '{event_rate}', num_events = '{num_events}',
  rate_limited = '{rate_limited}', batch_size = '{batch_size}',
  base_time_micros = '{base_time_micros}', seed = '{seed}'
);
