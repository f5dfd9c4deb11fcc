SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= to_date('1997-01-01')
  AND l_shipdate < to_date('1998-01-01')
  AND l_discount BETWEEN 0.06 AND 0.08
  AND l_quantity < 24
