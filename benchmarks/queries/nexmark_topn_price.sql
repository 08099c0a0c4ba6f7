WITH bids as (SELECT bid.auction as auction, bid.price as price
    FROM nexmark where bid is not null)
SELECT * FROM ( SELECT *, ROW_NUMBER()  OVER (
  PARTITION BY window
  ORDER BY price DESC) as row_number
FROM (
SELECT auction,
       hop(INTERVAL '2' second, INTERVAL '10' second ) as window,
       sum(price) as price
  FROM bids
  GROUP BY 1, 2)) WHERE row_number < 4
